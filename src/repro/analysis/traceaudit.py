"""Stage 2: trace-time audits over the actual drivers.

Where the AST lint reasons about source, this stage compiles the real
host, device, block, and sharded GMRES drivers on tiny synthetic
problems and checks invariants only traces make visible:

* **retrace** — a second same-shape solve must reuse the compiled
  program: the device/block drivers are probed with a counting user
  matvec (its Python body runs only while tracing), the host driver via
  the ``_HOST_KERNEL_CACHE`` it now shares across solves, the sharded
  driver via ``_SHARDED_CACHE`` — all cross-checked against each jitted
  function's ``_cache_size()`` where jax exposes it.
* **spec-mismatch** — ``driver_partition_specs`` /
  ``block_driver_partition_specs`` must structurally match the actual
  ``lax.while_loop`` state pytree (``jax.eval_shape`` of the un-jitted
  solve); a mismatch is reported as a per-path diff instead of the
  runtime shard_map error it would otherwise become.
* **f64-leak** — the cycle jaxpr of an frsz2-only policy at f32
  arithmetic must contain no f64 avals, f64 constants, or
  ``convert_element_type`` to f64 (checked with x64 *enabled*, so the
  check cannot pass vacuously).
* **transfer** — a warmed device/block solve must run to completion
  under ``jax.transfer_guard("disallow")``.

Determinism: every entry point pins ``repro.kernels.ops.INTERPRET =
True`` explicitly (the backend must not decide what CI measures) and
enables x64.  The sharded audits need 8 devices; the CLI
(``repro.analysis.__main__``) re-execs itself with
``--xla_force_host_platform_device_count=8`` to run
:func:`run_sharded_audits` in a child process.
"""
from __future__ import annotations

import importlib

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis.report import Finding

__all__ = [
    "run_local_audits",
    "run_sharded_audits",
    "audit_device_retrace",
    "audit_block_retrace",
    "audit_host_retrace",
    "audit_partition_specs",
    "audit_f64_purity",
    "audit_transfer_guard",
]

_AXIS = "basis"


def _pin_environment():
    """Make the audits deterministic regardless of caller environment."""
    jax.config.update("jax_enable_x64", True)     # f64 checks non-vacuous
    from repro.kernels import ops

    ops.INTERPRET = True                          # not the backend default


def _problem(n: int = 180):
    from repro.sparse import make_problem, rhs_for

    A, target = make_problem("synth:atmosmod", n)
    b, _ = rhs_for(A)
    return A, jnp.asarray(b), float(target)


def _trace_finding(audit: str, rule: str, message: str) -> Finding:
    return Finding(path=f"trace:{audit}", line=0, rule=rule, message=message)


def _jit_cache_size(fn):
    """Compiled-signature count of a jitted fn; None if jax hides it."""
    try:
        return int(fn._cache_size())
    except Exception:
        return None


# ---------------------------------------------------------------------------
# retrace audits
# ---------------------------------------------------------------------------


def audit_device_retrace() -> list[Finding]:
    """Two same-shape device solves must trace the matvec exactly once."""
    G = importlib.import_module("repro.solver.gmres")

    A, b, _ = _problem()
    calls = dict(n=0)

    def counting_mv(v):                           # python body runs per trace
        calls["n"] += 1
        return A.matvec(v)

    G._SOLVE_CACHE.clear()
    kw = dict(matvec=counting_mv, storage="float64", m=8, max_iters=240,
              target_rrn=1e-8)
    findings = []
    G.gmres(A, b, **kw)
    first = calls["n"]
    if first == 0:
        findings.append(_trace_finding(
            "device-retrace", "retrace",
            "counting matvec never ran — the audit problem did not "
            "exercise the device driver"))
    G.gmres(A, b, **kw)
    if calls["n"] != first:
        findings.append(_trace_finding(
            "device-retrace", "retrace",
            f"second same-shape device solve retraced the matvec "
            f"({first} -> {calls['n']} trace-time calls); the "
            "_SOLVE_CACHE key is unstable for repeated solves"))
    if len(G._SOLVE_CACHE) != 1:
        findings.append(_trace_finding(
            "device-retrace", "retrace",
            f"two identical device solves left {len(G._SOLVE_CACHE)} "
            "_SOLVE_CACHE entries (expected 1)"))
    else:
        size = _jit_cache_size(next(iter(G._SOLVE_CACHE.values()))[0])
        if size not in (None, 1):
            findings.append(_trace_finding(
                "device-retrace", "retrace",
                f"cached device solve compiled {size} signatures for one "
                "problem shape"))
    return findings


def audit_block_retrace() -> list[Finding]:
    """Same check for the block driver (one shared Krylov basis)."""
    G = importlib.import_module("repro.solver.gmres")
    from repro.solver.block import gmres_block

    A, b, _ = _problem()
    rng = np.random.default_rng(7)
    B = jnp.asarray(np.stack([np.asarray(b) * s
                              for s in rng.uniform(0.5, 2.0, size=3)]))
    calls = dict(n=0)

    def counting_mv(v):
        calls["n"] += 1
        return A.matvec(v)

    G._SOLVE_CACHE.clear()
    kw = dict(matvec=counting_mv, storage="float64", m=8, max_iters=240,
              target_rrn=1e-8)
    findings = []
    gmres_block(A, B, **kw)
    first = calls["n"]
    gmres_block(A, B, **kw)
    if calls["n"] != first:
        findings.append(_trace_finding(
            "block-retrace", "retrace",
            f"second same-shape block solve retraced the matvec "
            f"({first} -> {calls['n']} trace-time calls)"))
    if len(G._SOLVE_CACHE) != 1:
        findings.append(_trace_finding(
            "block-retrace", "retrace",
            f"two identical block solves left {len(G._SOLVE_CACHE)} "
            "_SOLVE_CACHE entries (expected 1)"))
    return findings


def audit_host_retrace() -> list[Finding]:
    """The host driver's cycle kernels must persist across solves."""
    G = importlib.import_module("repro.solver.gmres")

    A, b, target = _problem()
    G._HOST_KERNEL_CACHE.clear()
    kw = dict(storage="float64", m=8, max_iters=240, target_rrn=target,
              driver="host")
    findings = []
    G.gmres(A, b, **kw)
    first = len(G._HOST_KERNEL_CACHE)
    if first == 0:
        findings.append(_trace_finding(
            "host-retrace", "retrace",
            "host solve built its kernels outside _HOST_KERNEL_CACHE — "
            "every solve re-jits from scratch (the seed behaviour)"))
    G.gmres(A, b * 1.5, **kw)        # same shapes, different values
    if len(G._HOST_KERNEL_CACHE) != first:
        findings.append(_trace_finding(
            "host-retrace", "retrace",
            f"second same-shape host solve grew the kernel cache "
            f"({first} -> {len(G._HOST_KERNEL_CACHE)} entries); the key "
            "bakes in a per-solve value"))
    for (kernels, _pins) in G._HOST_KERNEL_CACHE.values():
        for fn in kernels:
            size = _jit_cache_size(fn)
            if size not in (None, 1):
                findings.append(_trace_finding(
                    "host-retrace", "retrace",
                    f"host cycle kernel compiled {size} signatures across "
                    "two same-shape solves — a per-solve array is a jit "
                    "closure constant instead of an argument"))
    return findings


# ---------------------------------------------------------------------------
# partition-spec structure audit
# ---------------------------------------------------------------------------


def _tree_paths(tree, is_leaf=None) -> set:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)
    return {jax.tree_util.keystr(kp) for kp, _ in flat}


def _diff_specs(audit: str, state, specs) -> list[Finding]:
    state_paths = _tree_paths(state)
    spec_paths = _tree_paths(specs, is_leaf=lambda x: isinstance(x, P))
    findings = []
    for path in sorted(state_paths - spec_paths):
        findings.append(_trace_finding(
            audit, "spec-mismatch",
            f"state leaf {path} has no PartitionSpec — shard_map would "
            "fail at runtime with a pytree structure error"))
    for path in sorted(spec_paths - state_paths):
        findings.append(_trace_finding(
            audit, "spec-mismatch",
            f"PartitionSpec {path} matches no while_loop state leaf — "
            "stale spec entry"))
    return findings


def audit_partition_specs(spec_fn=None, block_spec_fn=None) -> list[Finding]:
    """Spec trees must mirror the actual driver state pytrees.

    ``spec_fn``/``block_spec_fn`` default to the real builders in
    :mod:`repro.dist.sharding`; tests inject broken ones to assert the
    diff comes out readable.
    """
    from repro.dist.sharding import (
        block_driver_partition_specs,
        driver_partition_specs,
    )
    from repro.solver.block import build_block_solve
    from repro.solver.gmres import build_device_solve

    spec_fn = spec_fn or driver_partition_specs
    block_spec_fn = block_spec_fn or block_driver_partition_specs

    A, b, _ = _problem()
    kw = dict(storage="float64", m=6, max_iters=60, target_rrn=1e-8)
    solve, _ = build_device_solve(A, b, **kw)
    vec = jax.ShapeDtypeStruct(b.shape, b.dtype)
    state = jax.eval_shape(solve, vec, vec)
    findings = _diff_specs("driver-specs", state, spec_fn(_AXIS))

    B = jnp.stack([b, b * 2.0])
    bsolve, baccs = build_block_solve(A, B, **kw)
    bvec = jax.ShapeDtypeStruct(B.shape, B.dtype)
    bstate = jax.eval_shape(bsolve, bvec, bvec)
    findings += _diff_specs("block-driver-specs", bstate,
                            block_spec_fn(baccs, _AXIS))
    return findings


# ---------------------------------------------------------------------------
# f64-purity of the compressed-format cycle jaxpr
# ---------------------------------------------------------------------------

_F64 = np.dtype(np.float64)


def _sub_jaxprs(value):
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _walk_eqns(sub)


def audit_f64_purity() -> list[Finding]:
    """No f64 reachable in an frsz2-only cycle at f32 arithmetic.

    Runs with x64 *enabled* (see :func:`_pin_environment`), so a stray
    python-float promotion or dtype literal genuinely lands as f64 in the
    jaxpr instead of being masked by the x64-disabled downcast.
    """
    from repro.solver.gmres import build_device_solve

    A32, b, _ = _problem()
    # f32 operator: the audit policy is frsz2-only at f32 arithmetic
    import repro.sparse.csr as csr

    A = csr.CSR(indptr=A32.indptr, indices=A32.indices,
                data=A32.data.astype(jnp.float32), shape=A32.shape)
    b = b.astype(jnp.float32)
    solve, _ = build_device_solve(
        A, b, storage="frsz2_16", arith_dtype=jnp.float32, m=6,
        max_iters=60, target_rrn=1e-5)
    closed = jax.make_jaxpr(solve)(b, jnp.zeros_like(b))

    findings = []
    hits: dict[str, int] = {}
    for const in closed.consts:
        dtype = getattr(const, "dtype", None)
        if dtype is not None and np.dtype(dtype) == _F64:
            hits["const"] = hits.get("const", 0) + 1
    for eqn in _walk_eqns(closed.jaxpr):
        prim = eqn.primitive.name
        if (prim == "convert_element_type"
                and np.dtype(eqn.params["new_dtype"]) == _F64):
            hits["convert_element_type->f64"] = \
                hits.get("convert_element_type->f64", 0) + 1
            continue
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and np.dtype(dtype) == _F64:
                hits[prim] = hits.get(prim, 0) + 1
                break
    for what, count in sorted(hits.items()):
        findings.append(_trace_finding(
            "f64-purity", "f64-leak",
            f"{count}x {what} producing float64 inside the frsz2_16/f32 "
            "cycle jaxpr — precision escaped the StorageFormat protocol"))
    return findings


# ---------------------------------------------------------------------------
# transfer-guard sweep
# ---------------------------------------------------------------------------


def audit_transfer_guard() -> list[Finding]:
    """Warmed device drivers must run under transfer_guard('disallow')."""
    G = importlib.import_module("repro.solver.gmres")
    from repro.solver.block import gmres_block

    A, b, _ = _problem()
    findings = []

    G._SOLVE_CACHE.clear()
    kw = dict(storage="float64", m=8, max_iters=240, target_rrn=1e-8)
    G.gmres(A, b, **kw)                                    # warm + compile
    solve, args, _ = G.solve_program(A, b, **kw)
    args = jax.device_put(args)
    try:
        with jax.transfer_guard("disallow"):
            jax.block_until_ready(solve(*args))
    except Exception as e:                                  # noqa: BLE001
        findings.append(_trace_finding(
            "device-transfer", "transfer",
            f"device solve transfers under transfer_guard('disallow'): "
            f"{type(e).__name__}: {e}"))

    G._SOLVE_CACHE.clear()
    B = jnp.stack([b, b * 2.0])
    gmres_block(A, B, **kw)
    bsolve = next(iter(G._SOLVE_CACHE.values()))[0]
    Bd = jax.device_put(B)
    X0d = jax.device_put(jnp.zeros_like(B))
    try:
        with jax.transfer_guard("disallow"):
            jax.block_until_ready(bsolve(Bd, X0d))
    except Exception as e:                                  # noqa: BLE001
        findings.append(_trace_finding(
            "block-transfer", "transfer",
            f"block solve transfers under transfer_guard('disallow'): "
            f"{type(e).__name__}: {e}"))
    return findings


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def run_local_audits() -> list[Finding]:
    """Every audit that runs on the current (single-device) backend."""
    _pin_environment()
    findings: list[Finding] = []
    findings += audit_device_retrace()
    findings += audit_block_retrace()
    findings += audit_host_retrace()
    findings += audit_partition_specs()
    findings += audit_f64_purity()
    findings += audit_transfer_guard()
    return findings


def run_sharded_audits() -> list[Finding]:
    """Retrace audit for the sharded driver; needs >= 8 devices.

    Run via ``python -m repro.analysis --inner-sharded`` in a child
    process with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    (the CLI does this; the direct call is for tests that already own an
    8-device backend).
    """
    _pin_environment()
    G = importlib.import_module("repro.solver.gmres")
    S = importlib.import_module("repro.solver.sharded")

    if len(jax.devices()) < 8:
        return [_trace_finding(
            "sharded-retrace", "retrace",
            f"audit needs 8 devices, found {len(jax.devices())} — launch "
            "via the CLI, which forces 8 emulated host devices")]

    A, b, _ = _problem(256)
    S._SHARDED_CACHE.clear()
    kw = dict(storage="float64", m=8, max_iters=240, target_rrn=1e-8,
              shard=8)
    findings = []
    r1 = G.gmres(A, b, **kw)
    first = len(S._SHARDED_CACHE)
    r2 = G.gmres(A, b, **kw)
    if first != 1 or len(S._SHARDED_CACHE) != 1:
        findings.append(_trace_finding(
            "sharded-retrace", "retrace",
            f"two identical sharded solves left {len(S._SHARDED_CACHE)} "
            "_SHARDED_CACHE entries (expected 1)"))
    else:
        size = _jit_cache_size(next(iter(S._SHARDED_CACHE.values()))[0])
        if size not in (None, 1):
            findings.append(_trace_finding(
                "sharded-retrace", "retrace",
                f"cached sharded solve compiled {size} signatures for one "
                "problem shape"))
    if r1.iterations != r2.iterations:
        findings.append(_trace_finding(
            "sharded-retrace", "retrace",
            "repeated sharded solve diverged from its first run "
            f"({r1.iterations} vs {r2.iterations} iterations) — the "
            "cached program is not the one being reused"))
    return findings
