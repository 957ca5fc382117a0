"""Fused block-contraction kernels: oracle parity, layout
regressions, and the jaxpr-level proof that the frsz2 block cycle never
materializes the decoded basis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import frsz2 as F
from repro.core.accessor import (
    BlockBasisAccessor,
    FrszFormat,
    MixedFormat,
    NativeFormat,
)
from repro.kernels import ops

from tests._hypothesis_compat import given, settings, st

KSPECS = {
    32: F.FrszSpec(bs=128, l=32, dtype=jnp.float32),
    16: F.FrszSpec(bs=128, l=16, dtype=jnp.float32),
}


def _accessor_pair(spec, m, p, n, arith_dtype):
    k = BlockBasisAccessor(fmt=FrszFormat(spec, use_kernels=True), m=m, p=p,
                           n=n, arith_dtype=arith_dtype)
    j = BlockBasisAccessor(fmt=FrszFormat(spec, use_kernels=False), m=m, p=p,
                           n=n, arith_dtype=arith_dtype)
    return k, j


def _filled_stores(rng, acc_k, acc_j):
    sk, sj = acc_k.empty(), acc_j.empty()
    for j in range(acc_k.m):
        W = jnp.asarray(rng.standard_normal((acc_k.p, acc_k.n)),
                        acc_k.arith_dtype)
        sk = acc_k.write_block(sk, j, W)
        sj = acc_j.write_block(sj, j, W)
    return sk, sj


# ---------------------------------------------------------------------------
# property sweep: fused block contractions vs the pure-jnp oracle
# ---------------------------------------------------------------------------


@given(st.integers(1, 6), st.integers(1, 5), st.integers(3, 400),
       st.integers(0, 1))
@settings(max_examples=16, deadline=None)
def test_block_contractions_match_oracle(m, p, n, which):
    spec = KSPECS[[32, 16][which]]
    rng = np.random.default_rng(m * 100003 + p * 1009 + n)
    acc_k, acc_j = _accessor_pair(spec, m, p, n, jnp.float32)
    assert acc_k.n_seg % spec.bs == 0 and acc_k.nbytes() == acc_j.nbytes()
    ops_interpret, ops.INTERPRET = ops.INTERPRET, True
    try:
        sk, sj = _filled_stores(rng, acc_k, acc_j)
        W = jnp.asarray(rng.standard_normal((p, n)), jnp.float32)
        mask = jnp.arange(m) < max(m - 1, 1)
        Hk = acc_k.block_dots(sk, W, mask)
        Hj = acc_j.block_dots(sj, W, mask)
        np.testing.assert_allclose(np.asarray(Hk), np.asarray(Hj),
                                   rtol=2e-5, atol=2e-5)
        Y = jnp.asarray(rng.standard_normal((m, p, p)), jnp.float32)
        Ck = acc_k.block_combine(sk, Y, mask)
        Cj = acc_j.block_combine(sj, Y, mask)
        assert Ck.shape == (p, n)
        np.testing.assert_allclose(np.asarray(Ck), np.asarray(Cj),
                                   rtol=2e-5, atol=2e-5)
    finally:
        ops.INTERPRET = ops_interpret


def test_block_wrappers_decline_off_kernel_path():
    # unaligned spec: the wrappers return None and the format falls back
    spec = F.FrszSpec(bs=32, l=21, dtype=jnp.float64)
    acc_k, acc_j = _accessor_pair(spec, 3, 2, 100, jnp.float64)
    rng = np.random.default_rng(7)
    sk, sj = _filled_stores(rng, acc_k, acc_j)
    bc = acc_k.fmt._as_bc(sk, acc_k.n_flat)
    assert ops.block_dots(bc, jnp.zeros((2, 100)), p=2) is None
    assert ops.block_combine(bc, jnp.zeros((3, 2, 2)), p=2) is None
    W = jnp.asarray(rng.standard_normal((2, 100)))
    np.testing.assert_allclose(np.asarray(acc_k.block_dots(sk, W)),
                               np.asarray(acc_j.block_dots(sj, W)),
                               rtol=1e-12, atol=1e-12)


def test_mixed_block_store_routes_head_and_tail():
    spec = KSPECS[32]
    fmt_k = MixedFormat(k=2, head=NativeFormat(jnp.float32),
                        tail=FrszFormat(spec, use_kernels=True))
    fmt_j = MixedFormat(k=2, head=NativeFormat(jnp.float32),
                        tail=FrszFormat(spec, use_kernels=False))
    assert fmt_k.block_align() == 128
    m, p, n = 5, 3, 200
    acc_k = BlockBasisAccessor(fmt=fmt_k, m=m, p=p, n=n,
                               arith_dtype=jnp.float32)
    acc_j = BlockBasisAccessor(fmt=fmt_j, m=m, p=p, n=n,
                               arith_dtype=jnp.float32)
    rng = np.random.default_rng(11)
    ops_interpret, ops.INTERPRET = ops.INTERPRET, True
    try:
        sk, sj = _filled_stores(rng, acc_k, acc_j)
        W = jnp.asarray(rng.standard_normal((p, n)), jnp.float32)
        np.testing.assert_allclose(np.asarray(acc_k.block_dots(sk, W)),
                                   np.asarray(acc_j.block_dots(sj, W)),
                                   rtol=2e-5, atol=2e-5)
        Y = jnp.asarray(rng.standard_normal((m, p, p)), jnp.float32)
        np.testing.assert_allclose(np.asarray(acc_k.block_combine(sk, Y)),
                                   np.asarray(acc_j.block_combine(sj, Y)),
                                   rtol=2e-5, atol=2e-5)
    finally:
        ops.INTERPRET = ops_interpret


# ---------------------------------------------------------------------------
# layout regressions + memoization
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [7, 127])
def test_pick_block_rows_pads_odd_row_counts(m):
    m_pad, br = ops._pick_block_rows(m)
    assert m_pad % 8 == 0 and m_pad >= m
    assert br >= 8 and m_pad % br == 0


@pytest.mark.parametrize("m", [7, 127])
def test_odd_row_basis_roundtrip(m, rng):
    # wrapper-level regression: odd/prime row counts run the padded kernel
    # (never a row-per-grid-step launch) and still match the jnp codec
    spec = KSPECS[16]
    V = jnp.asarray(rng.standard_normal((m, 256)), jnp.float32)
    bc = ops.compress(V, spec, interpret=True)
    ref = F.compress(V, spec)
    assert np.array_equal(np.asarray(bc.codes), np.asarray(ref.codes))
    y = ops.decompress(bc, interpret=True)
    assert np.array_equal(np.asarray(y), np.asarray(F.decompress(ref)))


def test_layout_memoization_hits():
    spec = KSPECS[32]
    rng = np.random.default_rng(3)
    V = jnp.asarray(rng.standard_normal((6, 300)), jnp.float32)
    bc = F.compress(V, spec)
    x = jnp.asarray(rng.standard_normal(300), jnp.float32)
    ops.matvec(bc, x, interpret=True)
    before = ops._dot_layout.cache_info().hits
    ops.matvec(bc, x, interpret=True)
    assert ops._dot_layout.cache_info().hits > before
    acc, _ = _accessor_pair(spec, 3, 2, 300, jnp.float32)
    store = acc.empty()
    W = jnp.asarray(rng.standard_normal((2, 300)), jnp.float32)
    acc.block_dots(store, W)
    before = ops._block_layout.cache_info().hits
    acc.block_dots(store, W)
    assert ops._block_layout.cache_info().hits > before


# ---------------------------------------------------------------------------
# jaxpr-level fusion proof + end-to-end iteration parity
# ---------------------------------------------------------------------------


def _decoded_basis_avals(closed, forbidden):
    from repro.analysis.traceaudit import _walk_eqns

    hits = []
    for eqn in _walk_eqns(closed.jaxpr):
        for v in list(eqn.outvars) + list(eqn.invars):
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            if (tuple(aval.shape) in forbidden
                    and jnp.issubdtype(aval.dtype, jnp.floating)):
                hits.append((eqn.primitive.name, tuple(aval.shape),
                             str(aval.dtype)))
    return hits


@pytest.mark.parametrize("use_kernels", [True, False])
def test_block_cycle_jaxpr_fusion(use_kernels):
    """With the fused kernels the frsz2 block cycle jaxpr holds no decoded
    ``(m+1, p, n)`` (or flattened) basis intermediate; the jnp route (the
    control) does — proving the assertion has teeth."""
    from repro.core.accessor import format_by_name
    from repro.solver.block import build_block_solve
    from repro.sparse import make_problem

    ops_interpret, ops.INTERPRET = ops.INTERPRET, True
    try:
        A, _ = make_problem("synth:stencil27", 216)
        n = A.shape[0]
        m, p = 4, 3
        rng = np.random.default_rng(5)
        B = jnp.asarray(rng.standard_normal((p, n)))
        fmt = format_by_name("frsz2_32", use_kernels=use_kernels)
        solve, accs = build_block_solve(A, B, storage=fmt, ortho="cgs2",
                                        m=m, max_iters=2 * m,
                                        target_rrn=0.0)
        acc = accs[0]
        closed = jax.make_jaxpr(solve)(B, jnp.zeros_like(B))
        forbidden = {
            (acc.m, p, n), (acc.m, p, acc.n_seg),
            (acc.m, p * n), (acc.m, acc.n_flat),
        }
        hits = _decoded_basis_avals(closed, forbidden)
        if use_kernels:
            assert not hits, (
                f"fused block cycle materialized a decoded basis: {hits}")
        else:
            assert hits, ("the jnp control route should materialize the "
                          "decoded basis — the fusion assertion lost its "
                          "teeth")
    finally:
        ops.INTERPRET = ops_interpret


def test_block_gmres_iteration_parity_stencil27():
    """End-to-end: fused kernels change no iteration counts at p=8."""
    from repro.core.accessor import format_by_name
    from repro.solver.block import gmres_block
    from repro.sparse import make_problem

    A, _ = make_problem("synth:stencil27", 343)
    n = A.shape[0]
    p = 8
    rng = np.random.default_rng(9)
    B = jnp.asarray(rng.standard_normal((p, n)))
    B = B / jnp.linalg.norm(B, axis=1, keepdims=True)
    ops_interpret, ops.INTERPRET = ops.INTERPRET, True
    try:
        kw = dict(ortho="mgs", m=8, max_iters=48, target_rrn=1e-8)
        res_j = gmres_block(A, B, storage=format_by_name("frsz2_32"), **kw)
        res_k = gmres_block(
            A, B, storage=format_by_name("frsz2_32", use_kernels=True), **kw)
    finally:
        ops.INTERPRET = ops_interpret
    assert [r.iterations for r in res_k] == [r.iterations for r in res_j]
    assert [r.converged for r in res_k] == [r.converged for r in res_j]
    np.testing.assert_allclose(
        np.asarray([r.rrn for r in res_k]),
        np.asarray([r.rrn for r in res_j]), rtol=1e-6, atol=1e-12)
