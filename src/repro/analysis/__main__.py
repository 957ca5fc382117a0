"""CLI for the jaxlint gate: ``python -m repro.analysis --check``.

Modes
-----

``--check`` (default)
    Stage 1 AST lint over the full tree, the stage 2 trace audits, then
    the stage 3 spmdcheck (jaxpr collective-uniformity walk + traffic
    cross-audit).  Host/device/block drivers run in-process; anything
    needing the 8-device mesh runs in a child process re-exec'd with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (device count
    is fixed at jax import time, so the parent cannot set it for
    itself).  Exit 0 iff no findings.
``--lint-only`` / ``--audit-only`` / ``--spmd-only``
    Run one stage.  ``--paths`` restricts the lint to specific files or
    directories; ``--no-sharded`` skips the subprocess legs.
``--list-rules``
    Print the rule table with the institutional-memory rationale.
``--format {text,json,github}``
    ``json`` emits the findings as a JSON array (machine-readable, empty
    array when clean); ``github`` appends ``::error`` workflow
    annotations after the text report so violations land inline on the
    PR diff.

Determinism: the audits pin ``repro.kernels.ops.INTERPRET = True``
themselves, so results do not depend on the caller's shell.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.analysis.astlint import lint_paths
from repro.analysis.report import Finding, format_findings
from repro.analysis.rules import RULES

#: directories linted by default, relative to the repo root.
LINT_ROOTS = ("src", "tests", "benchmarks")

_CHILD_PREFIX = "JAXLINT-FINDINGS:"


def _repo_root() -> Path:
    # src/repro/analysis/__main__.py -> repo root is three levels above src
    return Path(__file__).resolve().parents[3]


def _default_lint_paths() -> list[str]:
    root = _repo_root()
    return [str(root / d) for d in LINT_ROOTS if (root / d).is_dir()]


def _run_lint(paths: list[str]) -> list[Finding]:
    return lint_paths(paths)


def _run_local_audits() -> list[Finding]:
    from repro.analysis.traceaudit import run_local_audits

    return run_local_audits()


def _run_local_spmd() -> list[Finding]:
    from repro.analysis.jaxprcheck import run_local_checks
    from repro.analysis.traffic import run_local_traffic

    return run_local_checks() + run_local_traffic()


def _run_child(flag: str, fallback_path: str, fallback_rule: str) -> list[Finding]:
    """Run one analyzer leg under 8 emulated host devices.

    ``--xla_force_host_platform_device_count`` only takes effect before
    jax initializes, so the 8-device legs always run in a fresh child
    process regardless of the parent's device count.
    """
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", flag],
        capture_output=True, text=True, env=env,
        cwd=str(_repo_root()), timeout=600,
    )
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith(_CHILD_PREFIX):
            payload = json.loads(line[len(_CHILD_PREFIX):])
            return [Finding(**d) for d in payload]
    return [Finding(
        path=fallback_path, line=0, rule=fallback_rule,
        message=(
            f"{flag} subprocess produced no result "
            f"(exit {proc.returncode}); stderr tail: "
            + " | ".join(proc.stderr.splitlines()[-3:])
        ),
    )]


def _run_sharded_subprocess() -> list[Finding]:
    return _run_child("--inner-sharded", "trace:sharded", "retrace")


def _run_spmd_subprocess() -> list[Finding]:
    return _run_child("--inner-spmd", "traffic:sharded", "wire-model")


def _emit_child_findings(findings: list[Finding]) -> int:
    payload = [dataclasses.asdict(f) for f in findings]
    print(_CHILD_PREFIX + json.dumps(payload))
    return 0


def _inner_sharded() -> int:
    """Child-process entry: stage 2 sharded audits, findings as JSON."""
    from repro.analysis.traceaudit import run_sharded_audits

    return _emit_child_findings(run_sharded_audits())


def _inner_spmd() -> int:
    """Child-process entry: stage 3 sharded traffic + uniformity walks."""
    from repro.analysis.traffic import run_sharded_traffic

    return _emit_child_findings(run_sharded_traffic())


def _list_rules() -> int:
    for rule in RULES.values():
        print(f"{rule.id}: {rule.summary}")
        print(textwrap.indent(textwrap.fill(rule.rationale, width=72), "    "))
        print()
    return 0


def _annotation_escape(text: str) -> str:
    return text.replace("%", "%25").replace("\r", "%0D").replace("\n", "%0A")


def _annotation(f: Finding) -> str:
    """One GitHub Actions ``::error`` workflow command per finding."""
    title = _annotation_escape(f"jaxlint[{f.rule}]")
    msg = _annotation_escape(f.message)
    if f.line:  # a real file location -> annotate the diff line
        return (f"::error file={f.path},line={f.line},col={f.col + 1},"
                f"title={title}::{msg}")
    # symbolic locations (trace:/jaxpr:/traffic:) carry the path in the text
    return f"::error title={title}::{_annotation_escape(f.path)}: {msg}"


def _report(findings: list[Finding], fmt: str, stages: list[str]) -> int:
    if fmt == "json":
        ordered = sorted(findings, key=lambda f: (f.path, f.line, f.col,
                                                  f.rule))
        print(json.dumps([dataclasses.asdict(f) for f in ordered], indent=2))
        return 1 if findings else 0
    if findings:
        print(format_findings(findings))
        if fmt == "github":
            for f in sorted(findings, key=lambda f: (f.path, f.line)):
                print(_annotation(f))
        print(f"jaxlint: {len(findings)} finding(s)")
        return 1
    print(f"jaxlint: clean ({', '.join(stages)})")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="JAX-aware static analysis + trace audit (jaxlint).",
    )
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--check", action="store_true",
                      help="lint + trace audits + spmdcheck (the CI gate; "
                           "default)")
    mode.add_argument("--lint-only", action="store_true",
                      help="stage 1 AST lint only")
    mode.add_argument("--audit-only", action="store_true",
                      help="stage 2 trace audits only")
    mode.add_argument("--spmd-only", action="store_true",
                      help="stage 3 spmdcheck only (jaxpr uniformity + "
                           "traffic cross-audit)")
    mode.add_argument("--list-rules", action="store_true",
                      help="print the rule table and exit")
    mode.add_argument("--inner-sharded", action="store_true",
                      help=argparse.SUPPRESS)  # child-process entry
    mode.add_argument("--inner-spmd", action="store_true",
                      help=argparse.SUPPRESS)  # child-process entry
    ap.add_argument("--paths", nargs="*", default=None, metavar="PATH",
                    help="restrict the lint to these files/directories")
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the 8-device subprocess legs")
    ap.add_argument("--format", choices=("text", "json", "github"),
                    default="text", dest="fmt",
                    help="report format (default: text)")
    args = ap.parse_args(argv)

    if args.list_rules:
        return _list_rules()
    if args.inner_sharded:
        return _inner_sharded()
    if args.inner_spmd:
        return _inner_spmd()

    one_stage = args.lint_only or args.audit_only or args.spmd_only
    do_lint = args.lint_only or not one_stage
    do_audit = args.audit_only or not one_stage
    do_spmd = args.spmd_only or not one_stage

    findings: list[Finding] = []
    stages: list[str] = []
    if do_lint:
        paths = args.paths if args.paths else _default_lint_paths()
        findings += _run_lint(paths)
        stages.append("lint")
    if do_audit:
        findings += _run_local_audits()
        if not args.no_sharded:
            findings += _run_sharded_subprocess()
        stages.append("audit" + ("" if args.no_sharded else "+sharded"))
    if do_spmd:
        findings += _run_local_spmd()
        if not args.no_sharded:
            findings += _run_spmd_subprocess()
        stages.append("spmd" + ("" if args.no_sharded else "+sharded"))

    return _report(findings, args.fmt, stages)


if __name__ == "__main__":
    sys.exit(main())
