"""Composable cycle pipeline: orthogonalizers, preconditioners, precision
policies, the content-keyed solve cache, and batched parity across formats."""
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, st

from repro.core.accessor import BasisAccessor, NativeFormat
from repro.solver import gmres
from repro.solver.gmres import _SOLVE_CACHE, _SOLVE_CACHE_SIZE, gmres_batched
from repro.solver.pipeline import (
    AdaptivePolicy,
    CGS2Orthogonalizer,
    JacobiPreconditioner,
    MGSOrthogonalizer,
    StaticPolicy,
    policy_by_name,
)
from repro.sparse import PROBLEMS, make_problem, operator_matvec, rhs_for


def _problem(name="synth:atmosmod", n=512):
    A, rrn = make_problem(name, n)
    b, x_sol = rhs_for(A)
    return A, b, x_sol, rrn


# ---------------------------------------------------------------------------
# preconditioner hook
# ---------------------------------------------------------------------------


def test_jacobi_strictly_fewer_iterations_on_suite():
    """Acceptance: the Jacobi-preconditioned device-driver solve converges
    in strictly fewer iterations than unpreconditioned on the problem
    where the diagonal actually varies, and never meaningfully regresses
    on the constant-diagonal problems (there Jacobi is an exact scalar
    scaling, so the iteration count is unchanged up to rounding)."""
    iters = {}
    for name in PROBLEMS:
        A, target = make_problem(name, 216)
        b, _ = rhs_for(A)
        kw = dict(m=30, max_iters=4000, target_rrn=target, driver="device")
        plain = gmres(A, b, **kw)
        jac = gmres(A, b, precond="jacobi", **kw)
        iters[name] = (plain.iterations, jac.iterations)
        assert jac.converged == plain.converged, name
        assert jac.iterations <= plain.iterations + 2, (name, iters[name])
    plain_vc, jac_vc = iters["synth:varcoef"]
    assert jac_vc < plain_vc, iters["synth:varcoef"]
    assert jac_vc < plain_vc / 5          # decisive, not marginal


def test_jacobi_host_device_parity():
    A, b, _, rrn = _problem("synth:varcoef", n=216)
    kw = dict(precond="jacobi", m=30, max_iters=4000, target_rrn=rrn)
    rh = gmres(A, b, driver="host", **kw)
    rd = gmres(A, b, driver="device", **kw)
    assert rh.iterations == rd.iterations
    assert rh.restarts == rd.restarts
    np.testing.assert_allclose(np.asarray(rh.x), np.asarray(rd.x),
                               rtol=1e-10, atol=1e-12)


def test_callable_preconditioner_hook_matches_jacobi():
    A, b, _, rrn = _problem("synth:varcoef", n=216)
    inv_d = 1.0 / A.diag()
    kw = dict(m=30, max_iters=4000, target_rrn=rrn)
    r_jac = gmres(A, b, precond="jacobi", **kw)
    r_fn = gmres(A, b, precond=lambda x: x * inv_d.astype(x.dtype), **kw)
    assert r_fn.iterations == r_jac.iterations
    np.testing.assert_allclose(np.asarray(r_fn.x), np.asarray(r_jac.x),
                               rtol=1e-12)


def test_jacobi_preserves_true_residual():
    """Right preconditioning: the reported RRN is the residual of the
    *original* system, so the returned x solves A x = b.  The check applies
    A as the solver does (``operator_matvec``: varcoef runs the DIA SpMV),
    so both residuals round alike at this ill-scaled problem's 4e-12."""
    A, b, x_sol, rrn = _problem("synth:varcoef", n=216)
    res = gmres(A, b, precond="jacobi", m=30, max_iters=4000,
                target_rrn=rrn)
    assert res.converged
    rrn_check = float(jnp.linalg.norm(b - operator_matvec(A)(res.x))
                      / jnp.linalg.norm(b))
    np.testing.assert_allclose(rrn_check, res.rrn, rtol=1e-6)
    err = float(jnp.linalg.norm(res.x - x_sol) / jnp.linalg.norm(x_sol))
    assert err < 1e-4


def test_jacobi_requires_diag():
    A, b, _, _ = _problem(n=216)
    with pytest.raises(ValueError, match="diag"):
        gmres(None, b, precond="jacobi", matvec=lambda v: A.matvec(v), m=5,
              max_iters=5)


def test_jacobi_zero_diagonal_guard():
    p = JacobiPreconditioner(jnp.asarray([2.0, 0.0, 4.0]))
    out = np.asarray(p.apply(jnp.asarray([1.0, 1.0, 1.0])))
    np.testing.assert_allclose(out, [0.5, 1.0, 0.25])


# ---------------------------------------------------------------------------
# precision policies
# ---------------------------------------------------------------------------


def test_adaptive_policy_matches_static_with_fewer_bytes():
    """Acceptance: adaptive f64->frsz2_32->frsz2_16 reaches the same final
    RRN as static frsz2_32 (within 1e-10) while reading fewer basis bytes
    (StorageFormat.nbytes accounting carried by the drivers)."""
    A, b, _, rrn = _problem()
    kw = dict(m=10, max_iters=6000, target_rrn=rrn)
    adap = gmres(A, b, policy="adaptive", **kw)
    stat = gmres(A, b, storage="frsz2_32", **kw)
    assert adap.converged and stat.converged
    assert abs(adap.rrn - stat.rrn) < 1e-10
    assert adap.bytes_read > 0 and stat.bytes_read > 0
    assert adap.bytes_read < stat.bytes_read


def test_adaptive_host_device_parity():
    A, b, _, rrn = _problem()
    kw = dict(policy="adaptive", m=10, max_iters=6000, target_rrn=rrn)
    rh = gmres(A, b, driver="host", **kw)
    rd = gmres(A, b, driver="device", **kw)
    assert rh.iterations == rd.iterations
    assert rh.restarts == rd.restarts
    np.testing.assert_allclose(rh.bytes_read, rd.bytes_read, rtol=1e-12)
    np.testing.assert_allclose(np.asarray(rh.x), np.asarray(rd.x),
                               rtol=1e-10, atol=1e-12)


def test_policy_name_parsing():
    pol = policy_by_name("adaptive")
    assert isinstance(pol, AdaptivePolicy) and len(pol.levels) == 3
    pol = policy_by_name("adaptive:float64,float32@0.001,frsz2_16@1e-8")
    assert [f.name for f in pol.levels] == ["float64", "float32", "frsz2_16"]
    assert pol.thresholds == (0.001, 1e-8)
    # level index is monotone as the residual falls
    assert int(pol.level(1.0, 0)) == 0
    assert int(pol.level(1e-4, 3)) == 1
    assert int(pol.level(1e-9, 9)) == 2
    stat = policy_by_name("static:frsz2_32")
    assert isinstance(stat, StaticPolicy) and stat.fmt.name == "frsz2_32"
    with pytest.raises(ValueError):
        policy_by_name("adaptive:float64,frsz2_32")   # missing threshold
    with pytest.raises(ValueError):
        policy_by_name("nonsense:float64")
    with pytest.raises(ValueError):
        AdaptivePolicy(levels=(NativeFormat(jnp.float64),) * 2,
                       thresholds=())
    with pytest.raises(ValueError, match="strictly decreasing"):
        policy_by_name("adaptive:float64,frsz2_32@1e-6,frsz2_16@1e-6")


def test_adaptive_auto_thresholds_derivation():
    """adaptive:auto derives the switch points from the target RRN and the
    format epsilons (thr_i = safety * target / eps_i), falling back to the
    fixed 1e-2/1e-6 defaults when no target is available."""
    fixed = policy_by_name("adaptive")
    no_target = policy_by_name("adaptive:auto")
    assert no_target.thresholds == fixed.thresholds == (1e-2, 1e-6)

    target = 4e-14
    pol = policy_by_name("adaptive:auto", target_rrn=target)
    assert [f.name for f in pol.levels] == ["float64", "frsz2_32",
                                            "frsz2_16"]
    eps32, eps16 = pol.levels[1].eps(), pol.levels[2].eps()
    assert eps32 == 2.0**-30 and eps16 == 2.0**-14
    np.testing.assert_allclose(pol.thresholds,
                               (0.5 * target / eps32, 0.5 * target / eps16))
    # strictly decreasing, as AdaptivePolicy requires
    assert pol.thresholds[0] > pol.thresholds[1] > 0
    # a tighter target pushes every switch point down (stays high-precision
    # longer); a looser target the other way — no per-problem constants
    tighter = policy_by_name("adaptive:auto", target_rrn=target / 100)
    looser = policy_by_name("adaptive:auto", target_rrn=target * 100)
    assert all(a < b < c for a, b, c in zip(
        tighter.thresholds, pol.thresholds, looser.thresholds))
    with pytest.raises(ValueError, match="positive"):
        AdaptivePolicy.from_target(pol.levels, 0.0)


def test_adaptive_auto_converges_to_target():
    """End to end: the derived ladder reaches the per-problem target on
    both drivers with identical restart schedules, and still reads fewer
    basis bytes than uniform float64 storage."""
    A, b, _, rrn = _problem()
    kw = dict(policy="adaptive:auto", m=10, max_iters=6000, target_rrn=rrn)
    rd = gmres(A, b, **kw)
    rh = gmres(A, b, driver="host", **kw)
    assert rd.converged and rd.rrn <= rrn
    assert rh.iterations == rd.iterations
    assert rh.restarts == rd.restarts
    f64 = gmres(A, b, storage="float64", m=10, max_iters=6000,
                target_rrn=rrn)
    assert rd.bytes_read < f64.bytes_read


def test_static_policy_matches_storage_argument():
    """policy='static:<fmt>' is the same code path as storage='<fmt>'."""
    A, b, _, rrn = _problem(n=256)
    kw = dict(m=20, max_iters=2000, target_rrn=rrn)
    r1 = gmres(A, b, storage="frsz2_32", **kw)
    r2 = gmres(A, b, policy="static:frsz2_32", **kw)
    assert r1.iterations == r2.iterations
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))


# ---------------------------------------------------------------------------
# orthogonalizers
# ---------------------------------------------------------------------------


def _nominal_bytes(iterations, m, passes, row_bytes):
    """Read-traffic model assuming full cycles + a partial last one and no
    extra (conditional) sweeps."""
    from repro.solver.gmres import _cycle_row_reads

    full, last = divmod(iterations, m)
    return sum(_cycle_row_reads(j, passes) * row_bytes
               for j in [m] * full + ([last] if last else []))


def test_cgs2_converges_with_parity_and_more_traffic():
    A, b, _, rrn = _problem()
    kw = dict(ortho="cgs2", m=40, max_iters=2000, target_rrn=rrn)
    rh = gmres(A, b, driver="host", **kw)
    rd = gmres(A, b, driver="device", **kw)
    assert rh.converged and rd.converged
    assert rh.iterations == rd.iterations
    r_mgs = gmres(A, b, m=40, max_iters=2000, target_rrn=rrn)
    # two unconditional sweeps read ~2x the *nominal* one-pass traffic; the
    # conditional scheme's actual traffic can approach parity when the
    # "twice is enough" criterion fires often (it does on this stencil),
    # but can never exceed cgs2's unconditional double sweep per iteration
    n = b.shape[0]
    assert rd.bytes_read > 1.5 * _nominal_bytes(r_mgs.iterations, 40, 1,
                                                8 * n)
    assert rd.bytes_read >= r_mgs.bytes_read
    # cgs2 itself has no conditional sweeps: its accounting is exactly the
    # two-pass nominal model
    assert rd.bytes_read == _nominal_bytes(rd.iterations, 40, 2, 8 * n)


def _orthonormalize(ortho, n, m, seed, eta=0.7071067811865475):
    """Feed nearly-dependent vectors through the orthogonalizer loop."""
    rng = np.random.default_rng(seed)
    acc = BasisAccessor(fmt=NativeFormat(jnp.float64), m=m + 1, n=n,
                        arith_dtype=jnp.float64)
    store = acc.empty()
    v = rng.standard_normal(n)
    store = acc.write_row(store, 0, jnp.asarray(v / np.linalg.norm(v)))
    rows = jnp.arange(m + 1)
    for j in range(m):
        # mostly inside the current span + a tiny new direction: the
        # hard case for one-shot orthogonalization
        prev = np.asarray(acc.read_row(store, j))
        w = jnp.asarray(prev + 1e-7 * rng.standard_normal(n))
        w, h, hj1, _ = ortho(acc, store, w, rows <= j, eta)
        store = acc.write_row(store, j + 1, w / jnp.maximum(hj1, 1e-300))
    V = np.asarray(acc.read_all(store))
    G = V @ V.T
    return np.abs(G - np.eye(m + 1)).max()


@settings(max_examples=8, deadline=None)
@given(st.integers(3, 10), st.integers(0, 10_000))
def test_cgs2_vs_mgs_orthogonality_property(m, seed):
    """Property: both schemes keep the basis orthonormal to near machine
    precision on adversarially correlated inputs; CGS-2 never needs the
    conditional branch to do it."""
    err_mgs = _orthonormalize(MGSOrthogonalizer(), 96, m, seed)
    err_cgs2 = _orthonormalize(CGS2Orthogonalizer(), 96, m, seed)
    assert err_cgs2 < 1e-12, (m, seed, err_cgs2)
    assert err_mgs < 1e-10, (m, seed, err_mgs)


def _near_identity_problem(n=96, eps=1e-5, seed=0):
    """A = I + eps*R: every Arnoldi direction is nearly inside the current
    span, so MGS's "twice is enough" criterion fires at every iteration."""
    from repro.sparse.csr import csr_from_coo

    rng = np.random.default_rng(seed)
    dense = np.eye(n) + eps * rng.standard_normal((n, n))
    rows, cols = np.nonzero(np.ones((n, n), bool))
    return csr_from_coo(rows, cols, dense[rows, cols], (n, n))


def test_mgs_reorth_traffic_accounted():
    """bytes_read must reflect *actual* orthogonalization passes: when the
    conditional re-orthogonalization fires, the dots+combine traffic
    exceeds the nominal passes==1 model (ISSUE 3 satellite)."""
    from repro.solver.gmres import _cycle_row_reads

    A = _near_identity_problem()
    n = A.shape[0]
    b = jnp.asarray(np.sin(np.arange(n)))
    kw = dict(storage="float64", m=10, max_iters=100, target_rrn=1e-12)
    rd = gmres(A, b, driver="device", **kw)
    rh = gmres(A, b, driver="host", **kw)
    assert rd.converged and rd.restarts == 1, (rd.iterations, rd.restarts)
    row_bytes = 8 * n
    nominal = _cycle_row_reads(rd.iterations, 1) * row_bytes
    # every live iteration j re-orthogonalized: the extra sweep at j reads
    # its j+1 live rows, so the exact extra row count is sum_{j<it}(j+1)
    extra = rd.iterations * (rd.iterations + 1) // 2
    expected = _cycle_row_reads(rd.iterations, 1, extra) * row_bytes
    assert rd.bytes_read > nominal, (rd.bytes_read, nominal)
    assert rd.bytes_read == expected, (rd.bytes_read, expected)
    # host and device account identically
    np.testing.assert_allclose(rh.bytes_read, rd.bytes_read, rtol=1e-12)


def test_mgs_traffic_bounded_by_single_and_double_pass_models():
    """MGS's actual accounting sits between the nominal one-pass model
    (reorth never fires) and the two-pass model (fires every iteration)."""
    A, b, _, rrn = _problem(n=216)
    res = gmres(A, b, storage="float64", m=20, max_iters=2000,
                target_rrn=rrn)
    assert res.converged
    row_bytes = 8 * b.shape[0]
    lo = _nominal_bytes(res.iterations, 20, 1, row_bytes)
    hi = _nominal_bytes(res.iterations, 20, 2, row_bytes)
    assert lo <= res.bytes_read <= hi, (lo, res.bytes_read, hi)


# ---------------------------------------------------------------------------
# batched driver across every registered format family + policies
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["float64", "float32", "float16",
                                 "frsz2_32", "frsz2_16",
                                 "mixed:2:frsz2_16"])
def test_gmres_batched_parity_all_formats(fmt):
    A, b, _, rrn = _problem(n=216)
    n = b.shape[0]
    B = jnp.stack([b, 1.5 * b + 0.1 * jnp.sin(jnp.arange(n))])
    kw = dict(storage=fmt, m=20, max_iters=2000, target_rrn=rrn)
    batched = gmres_batched(A, B, **kw)
    # the vmapped matvec fuses differently, so for the coarse formats the
    # residual's last few ULP can flip a restart decision by one iteration
    # (the seed batched test documents the same effect); exact for the
    # precise formats, +-2 for the coarse ones.
    slack = 0 if fmt in ("float64", "float32", "frsz2_32") else 2
    for i, rb in enumerate(batched):
        rs = gmres(A, B[i], driver="device", **kw)
        assert rb.converged and rs.converged, (fmt, i)
        assert abs(rb.iterations - rs.iterations) <= slack, (fmt, i)
        np.testing.assert_allclose(np.asarray(rb.x), np.asarray(rs.x),
                                   rtol=1e-6, atol=1e-8)


def test_gmres_batched_adaptive_policy_parity():
    A, b, _, rrn = _problem(n=216)
    n = b.shape[0]
    B = jnp.stack([b, 1.5 * b + 0.1 * jnp.sin(jnp.arange(n))])
    kw = dict(policy="adaptive", m=10, max_iters=2000, target_rrn=rrn)
    batched = gmres_batched(A, B, **kw)
    for i, rb in enumerate(batched):
        rs = gmres(A, B[i], driver="device", **kw)
        assert rb.converged and rs.converged, i
        assert rb.iterations == rs.iterations, i
        np.testing.assert_allclose(rb.bytes_read, rs.bytes_read, rtol=1e-12)


def test_gmres_batched_jacobi():
    A, b, _, rrn = _problem("synth:varcoef", n=216)
    B = jnp.stack([b, 2.0 * b])
    out = gmres_batched(A, B, precond="jacobi", m=30, max_iters=2000,
                        target_rrn=rrn)
    assert all(r.converged for r in out)


# ---------------------------------------------------------------------------
# content-keyed solve cache
# ---------------------------------------------------------------------------


def test_solve_cache_keys_on_operator_content():
    """Rebuilding the same problem must hit the cache, not grow it."""
    kw = dict(m=5, max_iters=10, target_rrn=1e-30)
    A1, _ = make_problem("synth:atmosmod", 64)
    b1, _ = rhs_for(A1)
    gmres(A1, b1, **kw)
    size_after_first = len(_SOLVE_CACHE)
    A2, _ = make_problem("synth:atmosmod", 64)     # same content, new object
    assert A2 is not A1 and A2.fingerprint() == A1.fingerprint()
    b2, _ = rhs_for(A2)
    gmres(A2, b2, **kw)
    assert len(_SOLVE_CACHE) == size_after_first


def test_solve_cache_eviction_is_bounded():
    """Distinct operators never grow the cache past its bound."""
    from repro.sparse.csr import CSR

    A0, _ = make_problem("synth:atmosmod", 64)
    b, _ = rhs_for(A0)
    data = np.asarray(A0.data)
    for i in range(_SOLVE_CACHE_SIZE + 3):
        Ai = CSR(A0.indptr, A0.indices,
                 jnp.asarray(data * (1.0 + 0.01 * i)), A0.shape)
        gmres(Ai, b, m=3, max_iters=3, target_rrn=1e-30)
        assert len(_SOLVE_CACHE) <= _SOLVE_CACHE_SIZE


def _solve_through(cache, A, b, **kw):
    """One solve through the entry point that fills ``cache``."""
    from repro.solver.block import gmres_block

    if cache == "block":
        return gmres_block(A, jnp.stack([b, 2.0 * b]), **kw)
    if cache == "host":
        return gmres(A, b, driver="host", **kw)
    if cache == "sharded":
        return gmres(A, b, shard=1, **kw)
    return gmres(A, b, **kw)


@pytest.mark.parametrize("cache, module, name", [
    ("device", "repro.solver.gmres", "_SOLVE_CACHE"),
    ("block", "repro.solver.gmres", "_SOLVE_CACHE"),
    ("host", "repro.solver.gmres", "_HOST_KERNEL_CACHE"),
    ("sharded", "repro.solver.sharded", "_SHARDED_CACHE"),
])
def test_compiled_solve_key_holds_the_pipeline(cache, module, name):
    """Every compiled-solve cache keys on the whole resolved pipeline: an
    identical second solve adds no entry, and a solve that differs in one
    pipeline field (the orthogonalizer, the target) adds exactly one."""
    import importlib

    entries = getattr(importlib.import_module(module), name)
    A, _ = make_problem("synth:atmosmod", 64)
    b, _ = rhs_for(A)
    kw = dict(m=4, max_iters=8, target_rrn=1e-30)
    entries.clear()
    _solve_through(cache, A, b, **kw)
    assert len(entries) == 1
    _solve_through(cache, A, b, **kw)
    assert len(entries) == 1
    _solve_through(cache, A, b, **dict(kw, ortho="cgs2"))
    assert len(entries) == 2
    _solve_through(cache, A, b, **dict(kw, target_rrn=1e-29))
    assert len(entries) == 3


def test_fingerprint_distinguishes_content():
    A0, _ = make_problem("synth:atmosmod", 64)
    from repro.sparse.csr import CSR

    A1 = CSR(A0.indptr, A0.indices, A0.data * 2.0, A0.shape)
    assert A0.fingerprint() != A1.fingerprint()
    E = A0.to_ell()
    assert isinstance(E.fingerprint(), str)
    np.testing.assert_allclose(np.asarray(E.diag()), np.asarray(A0.diag()),
                               rtol=1e-14)
