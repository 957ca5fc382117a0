"""GMRES / CB-GMRES behaviour: correctness, format ordering, restarts."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accessor import format_by_name
from repro.core.emulators import AbsQuantFormat, PwRelQuantFormat
from repro.solver import gmres
from repro.sparse import make_problem, rhs_for


def _small_problem(n=512):
    A, rrn = make_problem("synth:atmosmod", n)
    b, x_sol = rhs_for(A)
    return A, b, x_sol, rrn


def test_gmres_solves_to_target():
    A, b, x_sol, rrn = _small_problem()
    res = gmres(A, b, m=40, max_iters=2000, target_rrn=rrn)
    assert res.converged
    assert res.rrn <= rrn
    err = float(jnp.linalg.norm(res.x - x_sol) / jnp.linalg.norm(x_sol))
    assert err < 1e-10


def test_gmres_matches_dense_solve():
    A, b, x_sol, _ = _small_problem(216)
    res = gmres(A, b, m=60, max_iters=1000, target_rrn=1e-13)
    dense = np.linalg.solve(np.asarray(A.to_dense()), np.asarray(b))
    np.testing.assert_allclose(np.asarray(res.x), dense, rtol=1e-8,
                               atol=1e-10)


@pytest.mark.parametrize("fmt", ["float32", "frsz2_32", "frsz2_16",
                                 "float16"])
def test_cb_gmres_converges(fmt):
    A, b, x_sol, rrn = _small_problem()
    res = gmres(A, b, storage=fmt, m=40, max_iters=4000, target_rrn=rrn)
    assert res.converged, (fmt, res.rrn)


def test_format_iteration_ordering():
    """Paper Fig. 8 ordering: f64 <= frsz2_32 <= f32 <= f16 iterations."""
    A, b, _, rrn = _small_problem(1000)
    iters = {}
    for fmt in ["float64", "frsz2_32", "float32", "float16"]:
        res = gmres(A, b, storage=fmt, m=40, max_iters=6000, target_rrn=rrn)
        assert res.converged, fmt
        iters[fmt] = res.iterations
    assert iters["float64"] <= iters["frsz2_32"] <= iters["float32"] * 1.05
    assert iters["float32"] <= iters["float16"]


def test_restart_semantics():
    A, b, _, rrn = _small_problem()
    res = gmres(A, b, m=10, max_iters=3000, target_rrn=rrn)
    assert res.converged
    assert res.restarts >= 2            # forced multiple cycles
    # explicit residuals at restarts decrease overall
    assert res.restart_rrns[-1] < res.restart_rrns[0]


def test_emulated_compressor_storage():
    A, b, _, rrn = _small_problem()
    res = gmres(A, b, storage=AbsQuantFormat(eb=1e-10), m=40,
                max_iters=4000, target_rrn=rrn)
    assert res.converged
    res2 = gmres(A, b, storage=PwRelQuantFormat(eb=1e-6), m=40,
                 max_iters=4000, target_rrn=rrn)
    assert res2.converged


def test_widerange_pathology():
    """PR02R reproduction (paper Fig. 9b/10): the similarity-scaled
    problem gives every Krylov vector a permanent wide in-block exponent
    spread.  The block-shared-exponent format (frsz2) stalls; the
    per-value format (float32) converges — exactly the paper's PR02R
    ordering."""
    A, _ = make_problem("synth:widerange", 512)
    b, _ = rhs_for(A)
    res64 = gmres(A, b, storage="float64", m=40, max_iters=600,
                  target_rrn=1e-12)
    res32 = gmres(A, b, storage="float32", m=40, max_iters=600,
                  target_rrn=1e-12)
    res_f = gmres(A, b, storage="frsz2_32", m=40, max_iters=600,
                  target_rrn=1e-12)
    assert res64.converged
    assert res32.converged                       # per-value format is fine
    assert res_f.rrn > res64.rrn * 1e3           # block format stalls
    assert res_f.iterations > 2 * res64.iterations


def test_kernel_backed_accessor_matches_jnp():
    A, b, _, rrn = _small_problem()
    f_plain = format_by_name("frsz2_16", arith_dtype=jnp.float32, bs=128)
    f_kern = format_by_name("frsz2_16", arith_dtype=jnp.float32, bs=128,
                            use_kernels=True)
    r1 = gmres(A, b.astype(jnp.float32), storage=f_plain, m=20,
               max_iters=200, target_rrn=1e-5, arith_dtype=jnp.float32)
    r2 = gmres(A, b.astype(jnp.float32), storage=f_kern, m=20,
               max_iters=200, target_rrn=1e-5, arith_dtype=jnp.float32)
    assert abs(r1.iterations - r2.iterations) <= 2
    np.testing.assert_allclose(np.asarray(r1.x), np.asarray(r2.x),
                               rtol=1e-3, atol=1e-4)


def test_ell_spmv_matches_csr(rng):
    A, b, _, _ = _small_problem(216)
    E = A.to_ell()
    x = jnp.asarray(rng.standard_normal(A.shape[1]))
    np.testing.assert_allclose(np.asarray(A @ x), np.asarray(E @ x),
                               rtol=1e-12)


@pytest.mark.parametrize("storage,block", [("float32", False),
                                           ("frsz2_16", False),
                                           ("frsz2_16", True)])
def test_float32_solve_lowers_dots_at_highest(storage, block):
    """Every dense product of a float32 solve asks for full f32 precision
    by itself (a TPU's default pass rounds f32 operands to bfloat16), with
    no process-wide matmul-precision setting."""
    import jax

    from repro.solver.block import build_block_solve
    from repro.solver.gmres import build_device_solve

    assert jax.config.jax_default_matmul_precision is None
    A, _ = make_problem("synth:atmosmod", 512, dtype=np.float32)
    b, _ = rhs_for(A)
    kw = dict(storage=storage, max_iters=40, target_rrn=1e-6,
              arith_dtype=jnp.float32)
    if block:
        b = jnp.stack([b, 2 * b + 1])
        solve, _ = build_block_solve(A, b, m=5, **kw)
    else:
        solve, _ = build_device_solve(A, b, m=10, **kw)
    text = jax.jit(solve).lower(b, jnp.zeros_like(b)).as_text()
    dots = [ln for ln in text.splitlines() if "stablehlo.dot_general" in ln]
    assert dots
    low = [ln.strip() for ln in dots
           if "precision = [HIGHEST, HIGHEST]" not in ln]
    assert not low, low
