"""Tests for the jaxlint gate: per-rule lint fixtures + trace-audit seams.

The lint fixtures are source snippets, one bad/good pair per rule, checked
through :func:`repro.analysis.lint_source` — no files on disk, no jax
tracing.  The trace-audit tests exercise the injectable seams
(``spec_fn``/``block_spec_fn``) so a deliberately broken spec tree proves
the diff comes out readable, and run the transfer-guard sweep under its
own marker (CI runs ``pytest -m transfer_guard`` as a separate step).
"""
import jax
import pytest

from repro.analysis import lint_paths, lint_source

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def rules_of(findings):
    return sorted({f.rule for f in findings})


def lint(src, path="fixture.py"):
    return lint_source(src, path)


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------


def test_host_sync_if_on_traced_arg():
    findings = lint(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n"
    )
    assert rules_of(findings) == ["host-sync"]
    assert findings[0].line == 4


def test_host_sync_float_cast_and_item():
    findings = lint(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    a = float(x)\n"
        "    b = x.item()\n"
        "    return a + b\n"
    )
    assert [f.line for f in findings] == [4, 5]
    assert rules_of(findings) == ["host-sync"]


def test_host_sync_numpy_call_on_traced_value():
    findings = lint(
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.linalg.norm(x)\n"
    )
    assert rules_of(findings) == ["host-sync"]


def test_host_sync_while_loop_body_is_traced():
    findings = lint(
        "import jax\n"
        "def solve(b):\n"
        "    def body(s):\n"
        "        if s > 0:\n"
        "            return s - 1\n"
        "        return s\n"
        "    return jax.lax.while_loop(lambda s: s > 0, body, b)\n"
    )
    assert rules_of(findings) == ["host-sync"]


def test_host_sync_static_attrs_ok():
    # shape/ndim/dtype are static under tracing — legitimate Python control
    # flow, must NOT be flagged.
    findings = lint(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x.ndim > 1:\n"
        "        x = x.sum(axis=0)\n"
        "    n = len(x.shape)\n"
        "    return x * n\n"
    )
    assert findings == []


def test_host_sync_untraced_function_ok():
    findings = lint(
        "def prep(x):\n"
        "    if x > 0:\n"
        "        return float(x)\n"
        "    return 0.0\n"
    )
    assert findings == []


def test_host_sync_nested_builder_params_not_tainted():
    # A nested def called with static Python values during the trace (the
    # run_cycle_at(k) pattern) must not inherit taint onto its own params.
    findings = lint(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    def at(k):\n"
        "        if k == 0:\n"
        "            return x\n"
        "        return x * k\n"
        "    return at(0) + at(1)\n"
    )
    assert findings == []


# ---------------------------------------------------------------------------
# f64-literal
# ---------------------------------------------------------------------------


def test_f64_astype_in_jit():
    findings = lint(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.astype('float64')\n"
    )
    assert rules_of(findings) == ["f64-literal"]


def test_f64_dtype_kwarg_and_jnp_float64():
    findings = lint(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    z = jnp.zeros(3, dtype=jnp.float64)\n"
        "    return z + jnp.float64(x)\n"
    )
    assert rules_of(findings) == ["f64-literal"]
    assert len(findings) == 2


def test_f64_outside_traced_code_ok():
    # Host-side prep legitimately pins f64 (the paper's arithmetic dtype).
    findings = lint(
        "import numpy as np\n"
        "def prep(a):\n"
        "    return np.asarray(a, dtype='float64')\n"
    )
    assert findings == []


# ---------------------------------------------------------------------------
# carry-drop
# ---------------------------------------------------------------------------

_CARRY_BAD = (
    "import jax\n"
    "def solve(b):\n"
    "    init = {'x': b, 'converged': False, 'stagnated': False}\n"
    "    def cond(s):\n"
    "        return ~s['converged']\n"
    "    def body(s):\n"
    "        return {'x': s['x'] + 1, 'converged': s['converged']}\n"
    "    return jax.lax.while_loop(cond, body, init)\n"
)


def test_carry_drop_while_loop_branch():
    findings = lint(_CARRY_BAD)
    assert rules_of(findings) == ["carry-drop"]
    assert "stagnated" in findings[0].message


def test_carry_drop_open_dict_ok():
    findings = lint(
        "import jax\n"
        "def solve(b):\n"
        "    init = {'x': b, 'converged': False, 'stagnated': False}\n"
        "    def cond(s):\n"
        "        return ~s['converged']\n"
        "    def body(s):\n"
        "        return {**s, 'x': s['x'] + 1}\n"
        "    return jax.lax.while_loop(cond, body, init)\n"
    )
    assert findings == []


def test_carry_drop_cond_branches():
    findings = lint(
        "import jax\n"
        "def step(pred, s):\n"
        "    return jax.lax.cond(\n"
        "        pred,\n"
        "        lambda s: {'x': s['x'], 'done': True},\n"
        "        lambda s: {'x': s['x'] + 1},\n"
        "        s)\n"
    )
    assert rules_of(findings) == ["carry-drop"]
    assert "done" in findings[0].message


# ---------------------------------------------------------------------------
# raw-collective
# ---------------------------------------------------------------------------


def test_raw_collective_attribute_call():
    findings = lint(
        "import jax\n"
        "def reduce(x, axis):\n"
        "    return jax.lax.psum(x, axis)\n",
        path="src/repro/solver/somewhere.py",
    )
    assert rules_of(findings) == ["raw-collective"]


def test_raw_collective_from_import():
    findings = lint(
        "from jax.lax import ppermute\n"
        "def shift(x, axis, perm):\n"
        "    return ppermute(x, axis, perm)\n",
        path="src/repro/sparse/somewhere.py",
    )
    assert rules_of(findings) == ["raw-collective"]


def test_raw_collective_allowed_in_collectives_home():
    findings = lint(
        "import jax\n"
        "def psum(x, axis):\n"
        "    return jax.lax.psum(x, axis)\n",
        path="src/repro/dist/collectives.py",
    )
    assert findings == []


def test_raw_collective_lax_module_alias():
    findings = lint(
        "from jax import lax as L\n"
        "def reduce(x, axis):\n"
        "    return L.psum(x, axis)\n",
        path="src/repro/solver/somewhere.py",
    )
    assert rules_of(findings) == ["raw-collective"]


def test_raw_collective_import_jax_lax_as():
    findings = lint(
        "import jax.lax as jl\n"
        "def shift(x, axis, perm):\n"
        "    return jl.ppermute(x, axis, perm)\n",
        path="src/repro/sparse/somewhere.py",
    )
    assert rules_of(findings) == ["raw-collective"]


def test_raw_collective_renamed_from_import():
    findings = lint(
        "from jax.lax import psum as p\n"
        "def reduce(x, axis):\n"
        "    return p(x, axis)\n",
        path="src/repro/solver/somewhere.py",
    )
    assert rules_of(findings) == ["raw-collective"]
    assert "lax.psum" in findings[0].message


def test_raw_collective_via_functools_partial():
    findings = lint(
        "import functools\n"
        "from jax import lax\n"
        "shift = functools.partial(lax.ppermute, axis_name='basis')\n",
        path="src/repro/sparse/somewhere.py",
    )
    assert rules_of(findings) == ["raw-collective"]
    assert "functools.partial" in findings[0].message


def test_partial_of_noncollective_ok():
    findings = lint(
        "import functools\n"
        "from jax import lax\n"
        "clip = functools.partial(lax.clamp, 0.0)\n",
        path="src/repro/solver/somewhere.py",
    )
    assert findings == []


def test_axis_index_is_not_a_collective():
    # axis_index costs no wire — deliberately outside the primitive set.
    findings = lint(
        "import jax\n"
        "def who(axis):\n"
        "    return jax.lax.axis_index(axis)\n",
        path="src/repro/sparse/shard.py",
    )
    assert findings == []


# ---------------------------------------------------------------------------
# pragmas
# ---------------------------------------------------------------------------


def test_pragma_ok_suppresses_named_rule():
    findings = lint(
        "import jax\n"
        "@jax.jit\n"
        "def f(x, steps=3):\n"
        "    n = int(steps)  # jaxlint: ok[host-sync] static config\n"
        "    return x * n\n"
    )
    assert findings == []


def test_pragma_ok_wrong_rule_does_not_suppress():
    findings = lint(
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return float(x)  # jaxlint: ok[f64-literal]\n"
    )
    assert rules_of(findings) == ["host-sync"]


def test_pragma_traced_marks_function():
    # Without the pragma the scanner has no evidence `solve` is traced;
    # with it, the body is checked.
    src = (
        "def solve(b, x0):{pragma}\n"
        "    if b > 0:\n"
        "        return b\n"
        "    return x0\n"
    )
    assert lint(src.format(pragma="")) == []
    findings = lint(src.format(pragma="  # jaxlint: traced"))
    assert rules_of(findings) == ["host-sync"]


# ---------------------------------------------------------------------------
# full tree
# ---------------------------------------------------------------------------


def test_full_tree_is_clean():
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1]
    paths = [str(root / d) for d in ("src", "tests", "benchmarks")
             if (root / d).is_dir()]
    findings = lint_paths(paths)
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# trace audit: seeded partition-spec mismatch must produce a readable path
# ---------------------------------------------------------------------------


def test_seeded_spec_mismatch_reports_readable_path():
    from repro.analysis.traceaudit import audit_partition_specs
    from repro.dist.sharding import (
        block_driver_partition_specs,
        driver_partition_specs,
    )

    def broken(axis, **kw):
        specs = dict(driver_partition_specs(axis, **kw))
        del specs["stagnated"]          # the PR 3 bug, seeded on purpose
        specs["bogus_extra"] = specs["converged"]
        return specs

    findings = audit_partition_specs(spec_fn=broken,
                                     block_spec_fn=block_driver_partition_specs)
    msgs = "\n".join(f.message for f in findings)
    assert any(f.rule == "spec-mismatch" for f in findings)
    # both directions of the diff, each naming the offending leaf by path
    assert "stagnated" in msgs and "bogus_extra" in msgs


def test_real_specs_match_driver_state():
    from repro.analysis.traceaudit import audit_partition_specs

    assert audit_partition_specs() == []


# ---------------------------------------------------------------------------
# transfer guard (own marker: CI runs `pytest -m transfer_guard` as a step)
# ---------------------------------------------------------------------------


@pytest.mark.transfer_guard
def test_device_driver_clean_under_transfer_guard():
    from repro.analysis.traceaudit import _pin_environment, audit_transfer_guard

    _pin_environment()
    findings = audit_transfer_guard()
    assert findings == [], "\n".join(f.render() for f in findings)


@pytest.mark.transfer_guard
def test_transfer_guard_audit_catches_a_transfer():
    # Control: the guard itself must actually fire on a host->device
    # transfer, or the clean result above proves nothing.
    import numpy as np

    with pytest.raises(Exception, match="[Dd]isallow"), \
            jax.transfer_guard("disallow"):
        jax.numpy.sin(np.ones(4)).block_until_ready()


# ---------------------------------------------------------------------------
# CLI: output formats + rule registry
# ---------------------------------------------------------------------------

_BAD_SRC = (
    "import jax\n"
    "@jax.jit\n"
    "def f(x):\n"
    "    return float(x)\n"
)


def test_stage3_rules_registered():
    from repro.analysis.rules import RULES

    for rule in ("nonuniform-collective", "bad-permutation",
                 "axis-mismatch", "wire-model", "reads-model"):
        assert rule in RULES and RULES[rule].rationale


def test_cli_format_json(tmp_path, capsys):
    import json

    from repro.analysis.__main__ import main

    bad = tmp_path / "bad.py"
    bad.write_text(_BAD_SRC)
    rc = main(["--lint-only", "--paths", str(bad), "--format", "json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert [f["rule"] for f in payload] == ["host-sync"]
    assert payload[0]["path"] == str(bad) and payload[0]["line"] == 4


def test_cli_format_json_clean_is_empty_array(tmp_path, capsys):
    import json

    from repro.analysis.__main__ import main

    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    rc = main(["--lint-only", "--paths", str(good), "--format", "json"])
    assert rc == 0
    assert json.loads(capsys.readouterr().out) == []


def test_cli_format_github_annotations(tmp_path, capsys):
    from repro.analysis.__main__ import main

    bad = tmp_path / "bad.py"
    bad.write_text(_BAD_SRC)
    rc = main(["--lint-only", "--paths", str(bad), "--format", "github"])
    out = capsys.readouterr().out
    assert rc == 1
    assert f"::error file={bad},line=4," in out
    assert "title=jaxlint[host-sync]::" in out


def test_github_annotation_for_symbolic_location():
    from repro.analysis.__main__ import _annotation
    from repro.analysis.report import Finding

    f = Finding(path="jaxpr:device-driver", line=0, rule="wire-model",
                message="model disagrees\nby 8 bytes")
    ann = _annotation(f)
    assert ann.startswith("::error title=jaxlint[wire-model]::")
    assert "jaxpr:device-driver" in ann
    assert "\n" not in ann and "%0A" in ann    # newline escaped
