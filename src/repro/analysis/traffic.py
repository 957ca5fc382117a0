"""spmdcheck Part B: the compiled-traffic cross-audit of the bytes model.

The repo's perf claims all rest on hand-maintained byte accounting —
``exchange_bytes``/``gather_bytes``/``reduce_bytes``/``halo_bytes`` for the
wire, ``GmresResult.bytes_read``/``op_reads`` for the basis — and that
model has been wrong twice already (PR 3's re-orth undercount, PR 4's
``(P-1)x`` all-gather undercount).  This module re-derives the same
quantities *from the jaxpr*: operand aval sizes at each collective
equation, multiplied by trip counts recovered from the program structure
(``scan`` lengths are static; the restart ``while`` prices per cycle), and
asserts exact equality with the model — no tolerance, because both sides
count the same integers.

Pricing rules (per device, matching the model's conventions):

  * ``psum``/``pmean``/``pmax``/``pmin`` — each device ships its operand
    once (:func:`repro.dist.collectives.reduce_bytes`); scalar operands are
    norm reductions, vector operands are orthogonalization dot products.
  * ``all_gather`` — a ring gather forwards every other device's chunk:
    ``(axis_size - 1) x`` the operand (:func:`~repro.dist.collectives.gather_bytes`).
  * ``ppermute`` — the operand crosses one link once
    (:func:`~repro.dist.collectives.exchange_bytes`); a compressed halo's
    separate code/exponent ppermutes sum to exactly
    ``storage_nbytes(strip, spec)`` because the codec's aval layout *is*
    its wire layout.

Three audits:

  * **matvec wire** (8-device child): the gathered / halo / block3d
    partitioned matvec jaxprs priced against
    ``OperatorPlan.matvec_wire_bytes()``, plain and compressed.
  * **collective census** (8-device child): the full sharded-GMRES solve
    jaxpr, split into per-solve and per-cycle buckets, against
    ``benchmarks.shard_wire.cycle_wire_bytes``.
  * **basis reads** (local): a fixed-trajectory device solve
    (``target_rrn=0`` never converges, CGS2 never fires a conditional
    pass, ``max_iters = k*m`` forces exactly ``k`` full cycles) whose
    ``bytes_read`` must equal ``cycles x _cycle_row_reads(m) x row_bytes``
    with ``row_bytes`` taken from the *store avals*, and whose
    ``op_reads`` must equal ``1 + cycles x (m + 2)``.  The same audit
    runs against the *block* driver (shared basis, p right-hand sides,
    including the FRSZ2 fused-kernel route): one stored block row serves
    all p columns, so the identical per-row formula must hold with the
    block accessor's segment-aligned ``row_bytes`` — the fused kernels
    change how bytes are *read*, never how many.
"""
from __future__ import annotations

import importlib
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.jaxprcheck import _body_jaxpr, _open, check_jaxpr
from repro.analysis.report import Finding
from repro.analysis.rules import COLLECTIVE_PRIMITIVES
from repro.dist.collectives import reduce_bytes, rounds_defect

__all__ = ["price_program", "run_local_traffic", "run_sharded_traffic"]

_AXIS = "basis"
_REDUCE = frozenset({"psum", "pmean", "pmax", "pmin"})


def _finding(audit: str, rule: str, message: str) -> Finding:
    return Finding(path=f"traffic:{audit}", line=0, rule=rule,
                   message=message)


class _Unpriceable(Exception):
    """The jaxpr's traffic cannot be statically priced (which is itself a
    finding: the audited programs must keep their collectives under static
    trip counts)."""


# ---------------------------------------------------------------------------
# The pricing walker
# ---------------------------------------------------------------------------


def _site_price(eqn):
    """(category, per-device wire bytes) of one collective equation."""
    prim = eqn.primitive.name
    size = nbytes = 0
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        n = int(np.prod(aval.shape)) if aval.shape else 1
        size += n
        nbytes += n * np.dtype(aval.dtype).itemsize
    if prim == "ppermute":
        return "matvec", nbytes
    if prim == "all_gather":
        return "matvec", (int(eqn.params["axis_size"]) - 1) * nbytes
    if prim in _REDUCE:
        return ("norms" if size == 1 else "dots"), nbytes
    raise _Unpriceable(f"no wire-pricing rule for collective {prim!r}")


def _contains_collective(jaxpr) -> bool:
    from repro.analysis.traceaudit import _walk_eqns

    return any(e.primitive.name in COLLECTIVE_PRIMITIVES
               for e in _walk_eqns(jaxpr))


def _price(jaxpr, mult, bucket, acc, path=""):
    for i, eqn in enumerate(jaxpr.eqns):
        prim = eqn.primitive.name
        here = f"{path}/{prim}@{i}" if path else f"{prim}@{i}"
        if prim in COLLECTIVE_PRIMITIVES:
            cat, nbytes = _site_price(eqn)
            acc[bucket][cat] += mult * nbytes
        elif prim == "scan":
            _price(_open(eqn.params["jaxpr"]),
                   mult * int(eqn.params["length"]), bucket, acc,
                   here + "[body]")
        elif prim == "while":
            body = _open(eqn.params["body_jaxpr"])
            cond = _open(eqn.params["cond_jaxpr"])
            if bucket == "cycle":
                # a data-dependent inner loop (back-substitution, rotation
                # replay) has no static trip count — it must be wire-free
                if _contains_collective(body) or _contains_collective(cond):
                    raise _Unpriceable(
                        f"collective under the dynamic inner while at {here}")
                continue
            _price(body, 1, "cycle", acc, here + "[body]")
            _price(cond, 1, "cycle", acc, here + "[cond]")
        elif prim == "cond":
            # price the heaviest branch (the run-cycle side; the early-skip
            # branch is collective-free).  Uniformity of the *choice* is
            # Part A's job, not the pricer's.
            best = None
            for bi, br in enumerate(eqn.params["branches"]):
                trial = {"solve": Counter(), "cycle": Counter()}
                _price(_open(br), mult, bucket, trial, f"{here}[br{bi}]")
                tot = (sum(trial["solve"].values())
                       + sum(trial["cycle"].values()))
                if best is None or tot > best[0]:
                    best = (tot, trial)
            if best is not None:
                for buck in ("solve", "cycle"):
                    acc[buck].update(best[1][buck])
        else:
            sub = _body_jaxpr(eqn.params)
            if sub is not None:
                _price(sub, mult, bucket, acc, here)


def price_program(closed) -> dict:
    """Per-device wire bytes of a closed jaxpr, by bucket and category.

    Returns ``{"solve": {...}, "cycle": {...}}`` Counters keyed by
    ``dots``/``norms``/``matvec``: the ``solve`` bucket is everything on
    the static path (priced once, scans multiplied out), the ``cycle``
    bucket is the body of the outermost ``while`` (priced per trip —
    the restart loop's per-cycle traffic).  Raises :class:`_Unpriceable`
    for structures the model has no counterpart for.
    """
    acc = {"solve": Counter(), "cycle": Counter()}
    _price(_open(closed), 1, "solve", acc)
    return acc


def _cycle_model():
    try:
        from benchmarks.shard_wire import cycle_wire_bytes
    except ImportError:  # repo root not on sys.path (bare child process)
        sys.path.insert(0, str(Path(__file__).resolve().parents[3]))
        from benchmarks.shard_wire import cycle_wire_bytes
    return cycle_wire_bytes


# ---------------------------------------------------------------------------
# Local audit: GmresResult.bytes_read / op_reads on a fixed trajectory
# ---------------------------------------------------------------------------


def run_local_traffic() -> list[Finding]:
    """Cross-audit ``bytes_read``/``op_reads`` against the device jaxpr.

    ``target_rrn=0.0`` pins the trajectory statically: the residual never
    reaches zero so no early skip, no convergence, and no stagnation
    (stagnation requires an implicit-estimate hit) — with CGS2 (no
    conditional re-orth) and ``max_iters = k*m`` the solve runs exactly
    ``k`` full ``m``-iteration cycles.  Every factor of the expected
    accounting then comes from the program, not the model: row bytes from
    the avals of the store each cycle allocates (``acc.empty()``), the
    trip count from the cycle scan's ``length``.
    """
    from repro.analysis.traceaudit import _pin_environment, _problem
    from repro.solver.gmres import _cycle_row_reads, build_device_solve

    _pin_environment()
    findings: list[Finding] = []
    A, b, _ = _problem()
    m, k = 6, 3
    for storage in ("float64", "frsz2_32"):
        label = f"reads[{storage}]"
        solve, accs = build_device_solve(
            A, b, storage=storage, ortho="cgs2", m=m, max_iters=k * m,
            target_rrn=0.0)
        acc = accs[0]
        vec = jax.ShapeDtypeStruct(b.shape, b.dtype)

        aval_bytes = sum(
            int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(jax.eval_shape(acc.empty)))
        row_bytes = aval_bytes / acc.m
        model_row = acc.nbytes() / acc.m
        if row_bytes != model_row:
            findings.append(_finding(label, "reads-model", (
                f"store avals hold {row_bytes} B per basis row but "
                f"{type(acc.fmt).__name__}.nbytes() models {model_row} B — "
                "the storage accounting does not match the actual buffers")))
            continue

        from repro.analysis.traceaudit import _walk_eqns

        closed = jax.make_jaxpr(solve)(vec, vec)
        lengths = sorted({int(e.params["length"])
                          for e in _walk_eqns(closed.jaxpr)
                          if e.primitive.name == "scan"})
        if lengths != [m]:
            findings.append(_finding(label, "reads-model", (
                f"could not recover the cycle trip count from the jaxpr: "
                f"scan lengths {lengths}, expected exactly [{m}]")))
            continue

        state = jax.tree.map(np.asarray,
                             jax.jit(solve)(b, jnp.zeros_like(b)))
        cycles, total = int(state["cycles"]), int(state["total"])
        if cycles != k or total != k * m:
            findings.append(_finding(label, "reads-model", (
                f"fixed-trajectory assumption broke: ran {cycles} cycles / "
                f"{total} iterations, expected {k} cycles / {k * m} — "
                "the audit's premises no longer hold, fix the audit")))
            continue

        expect = float(cycles * _cycle_row_reads(m, 2, 0) * row_bytes)
        got = float(state["nbytes"])
        if got != expect:
            findings.append(_finding(label, "reads-model", (
                f"bytes_read reports {got} B but {cycles} cycles x "
                f"_cycle_row_reads({m}, passes=2) x {row_bytes} B/row "
                f"(from the store avals) = {expect} B")))
        expect_reads = 1.0 + cycles * (m + 2)
        got_reads = float(state["op_reads"])
        if got_reads != expect_reads:
            findings.append(_finding(label, "reads-model", (
                f"op_reads reports {got_reads} but the trajectory applies "
                f"the operator 1 + {cycles} x ({m} + 2) = "
                f"{expect_reads} times")))
    findings += _local_block_reads()
    return findings


def _local_block_reads() -> list[Finding]:
    """The block-driver half of the basis-reads audit.

    Same fixed trajectory (``target_rrn=0``, CGS2, ``max_iters = k*m``),
    but through :func:`repro.solver.block.build_block_solve` with ``p``
    right-hand sides — and with the FRSZ2 storage on its fused-kernel
    route, so the audit holds the decode-inside-contraction kernels to
    the exact same byte accounting as the jnp route: the shared block row
    (``p`` segment-aligned segments) is priced once per read, from the
    store avals.
    """
    from repro.analysis.traceaudit import _pin_environment, _problem, _walk_eqns
    from repro.core.accessor import format_by_name
    from repro.solver.block import build_block_solve
    from repro.solver.gmres import _cycle_row_reads

    _pin_environment()
    findings: list[Finding] = []
    A, _, _ = _problem()
    n = A.shape[0]
    m, k, p = 4, 2, 3
    rng = np.random.default_rng(0)
    B = jnp.asarray(rng.standard_normal((p, n)))
    B = B / jnp.linalg.norm(B, axis=1, keepdims=True)
    storages = (
        ("float64", "float64"),
        ("frsz2_32+kernels", format_by_name("frsz2_32", use_kernels=True)),
    )
    for name, storage in storages:
        label = f"block-reads[{name}]"
        solve, accs = build_block_solve(
            A, B, storage=storage, ortho="cgs2", m=m, max_iters=k * m,
            target_rrn=0.0)
        acc = accs[0]
        vec = jax.ShapeDtypeStruct(B.shape, B.dtype)

        shapes = jax.eval_shape(solve, vec, vec)
        aval_bytes = sum(
            int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(shapes["stores"]))
        row_bytes = aval_bytes / acc.m
        model_row = acc.nbytes() / acc.m
        if row_bytes != model_row:
            findings.append(_finding(label, "reads-model", (
                f"block store avals hold {row_bytes} B per basis row but "
                f"{type(acc.fmt).__name__}.nbytes() models {model_row} B — "
                "the segment-aligned storage accounting does not match the "
                "actual buffers")))
            continue

        closed = jax.make_jaxpr(solve)(vec, vec)
        lengths = sorted({int(e.params["length"])
                          for e in _walk_eqns(closed.jaxpr)
                          if e.primitive.name == "scan"})
        if m not in lengths:
            findings.append(_finding(label, "reads-model", (
                f"could not recover the block cycle trip count from the "
                f"jaxpr: scan lengths {lengths} do not include m={m}")))
            continue

        state = jax.tree.map(np.asarray,
                             jax.jit(solve)(B, jnp.zeros_like(B)))
        cycles = int(state["cycles"])
        total = np.asarray(state["total"])  # per-column iteration counts
        if cycles != k or not np.all(total == k * m):
            findings.append(_finding(label, "reads-model", (
                f"fixed-trajectory assumption broke: ran {cycles} block "
                f"cycles / per-column iterations {total.tolist()}, "
                f"expected {k} cycles / {k * m} everywhere — the audit's "
                "premises no longer hold, fix the audit")))
            continue

        expect = float(cycles * _cycle_row_reads(m, 2, 0) * row_bytes)
        got = float(state["nbytes"])
        if got != expect:
            findings.append(_finding(label, "reads-model", (
                f"block bytes_read reports {got} B but {cycles} cycles x "
                f"_cycle_row_reads({m}, passes=2) x {row_bytes} B/row "
                f"(from the store avals, one shared row for all p={p} "
                f"right-hand sides) = {expect} B")))
        expect_reads = 1.0 + cycles * (m + 2)
        got_reads = float(state["op_reads"])
        if got_reads != expect_reads:
            findings.append(_finding(label, "reads-model", (
                f"block op_reads reports {got_reads} but the trajectory "
                f"applies the batched operator 1 + {cycles} x ({m} + 2) = "
                f"{expect_reads} times")))
    return findings


# ---------------------------------------------------------------------------
# Sharded audits: matvec wire + full-solve census (8-device child)
# ---------------------------------------------------------------------------


def _matvec_jaxpr(plan, compressed: bool):
    from jax.sharding import Mesh
    from repro.dist.sharding import vector_partition_spec
    from repro.sparse.shard import partition_matvec

    mesh = Mesh(np.asarray(jax.devices()[:plan.n_shards]), (_AXIS,))
    operand, op_specs, local_mv = partition_matvec(
        plan=plan, axis_name=_AXIS, mesh=mesh, compressed_halo=compressed)
    vspec = vector_partition_spec(_AXIS)
    sm = jax.shard_map(lambda op, v: local_mv(op, v), mesh=mesh,
                      in_specs=(op_specs, vspec), out_specs=vspec,
                      axis_names={_AXIS}, check_vma=False)
    vec = jax.ShapeDtypeStruct((plan.n_pad,), jnp.float64)
    return jax.make_jaxpr(sm)(operand, vec)


def _audit_matvec(plan, mode_label: str, compressed: bool,
                  findings: list[Finding]):
    label = f"matvec[{mode_label}{'+frsz2' if compressed else ''}]"
    closed = _matvec_jaxpr(plan, compressed)
    _sites, f = check_jaxpr(closed, label=label)
    findings += f
    try:
        acc = price_program(closed)
    except _Unpriceable as exc:
        findings.append(_finding(label, "wire-model", str(exc)))
        return
    if acc["cycle"]:
        findings.append(_finding(label, "wire-model", (
            "a partitioned matvec priced traffic under a while loop "
            f"({dict(acc['cycle'])}) — its exchanges must be loop-free")))
    got = sum(acc["solve"].values())
    extra = got - acc["solve"].get("matvec", 0)
    if extra:
        findings.append(_finding(label, "wire-model", (
            f"a partitioned matvec moved {extra} non-operand wire bytes "
            f"({dict(acc['solve'])}) — it should only ship operand chunks")))
    want = plan.matvec_wire_bytes(compressed=compressed, dtype=jnp.float64)
    if got != want:
        findings.append(_finding(label, "wire-model", (
            f"the {plan.matvec_mode} matvec jaxpr moves {got} B/device but "
            f"plan.matvec_wire_bytes(compressed={compressed}) models "
            f"{want} B")))


def _sharded_solve_jaxpr(plan, m: int):
    G = importlib.import_module("repro.solver.gmres")
    S = importlib.import_module("repro.solver.sharded")

    vec = jax.ShapeDtypeStruct((plan.n_pad,), jnp.float64)
    pipe = G._resolve(plan.operator, vec, "float64", None, m, None, None,
                      None, "cgs2", 1e-8, axis_name=_AXIS,
                      rows=(plan.n_local, plan.n_pad))
    solve, operand = S._build_sharded_solve(
        plan, pipe, False, "vmap", m, 4 * m, 0.7071067811865475, 1e-8,
        _AXIS, False)
    return jax.make_jaxpr(solve)(vec, vec, operand)


def _audit_census(plan, m: int, findings: list[Finding]):
    """Price the whole sharded solve and hold it to ``cycle_wire_bytes``."""
    label = f"census[{plan.matvec_mode}]"
    closed = _sharded_solve_jaxpr(plan, m)
    _sites, f = check_jaxpr(closed, label=label)
    findings += f
    try:
        acc = price_program(closed)
    except _Unpriceable as exc:
        findings.append(_finding(label, "wire-model", str(exc)))
        return
    w = plan.matvec_wire_bytes(dtype=jnp.float64)
    model = _cycle_model()(m, j_stop=m, reorth=0, passes=2,
                           dots_compressed=False, norms_compressed=False,
                           inner_mv_bytes=w, residual_mv_bytes=w)
    want = {
        "cycle": {"dots": model["dots"], "norms": model["norms"],
                  "matvec": model["matvec"]},
        # before the loop: ||b|| + the rrn0 residual (one exact matvec
        # exchange + one scalar psum)
        "solve": {"norms": 2 * reduce_bytes(1, compressed=False),
                  "matvec": w},
    }
    for bucket, wanted in want.items():
        got = dict(acc[bucket])
        for cat in sorted(set(wanted) | set(got)):
            g, e = got.get(cat, 0), wanted.get(cat, 0)
            if g != e:
                findings.append(_finding(label, "wire-model", (
                    f"per-{bucket} {cat} traffic: the jaxpr moves {g} "
                    f"B/device but the model prices {e} B (CGS2, m={m}, "
                    f"j_stop={m}, matvec mode {plan.matvec_mode})")))
    return


def run_sharded_traffic() -> list[Finding]:
    """Matvec wire + census audits; needs >= 8 devices.

    Run via ``python -m repro.analysis --inner-spmd`` in a child process
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CLI
    does this; the direct call is for tests that own an 8-device backend).
    """
    from repro.analysis.traceaudit import _pin_environment

    _pin_environment()
    if len(jax.devices()) < 8:
        return [_finding(
            "sharded", "wire-model",
            f"audit needs 8 devices, found {len(jax.devices())} — launch "
            "via the CLI, which forces 8 emulated host devices")]
    from repro.sparse import make_problem, plan_operator

    findings: list[Finding] = []
    A, _ = make_problem("synth:atmosmod", 256)
    rows_plan = plan_operator(A, 8, reorder="none", matvec_mode="rows")
    S27, _ = make_problem("synth:stencil27", 512)
    halo_plan = plan_operator(S27, 8, reorder="none", matvec_mode="halo")
    block_plan = plan_operator(S27, 8, reorder="none",
                               matvec_mode="block3d")

    # the 3-D exchange schedule itself: every round a partial injection,
    # no channel reused across rounds (shared definition with the runtime
    # guard in halo_exchange_3d and the property tests)
    defect = rounds_defect(block_plan.block.rounds, block_plan.n_shards)
    if defect is not None:
        findings.append(_finding(
            "rounds[block3d]", "bad-permutation",
            f"block partition exchange schedule is malformed: {defect}"))

    _audit_matvec(rows_plan, "rows", False, findings)
    _audit_matvec(halo_plan, "halo", False, findings)
    _audit_matvec(halo_plan, "halo", True, findings)
    _audit_matvec(block_plan, "block3d", False, findings)
    _audit_matvec(block_plan, "block3d", True, findings)
    _audit_census(rows_plan, 8, findings)
    return findings
