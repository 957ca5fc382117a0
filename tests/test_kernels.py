"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import frsz2 as F
from repro.kernels import frsz2_dot as KD
from repro.kernels import ops, ref

KSPECS = [
    F.FrszSpec(bs=128, l=32, dtype=jnp.float32),
    F.FrszSpec(bs=128, l=16, dtype=jnp.float32),
    F.FrszSpec(bs=128, l=8, dtype=jnp.float32),
    F.FrszSpec(bs=64, l=16, dtype=jnp.float32),
    F.FrszSpec(bs=32, l=16, dtype=jnp.float32),
]


@pytest.mark.parametrize("spec", KSPECS, ids=lambda s: s.name)
@pytest.mark.parametrize("shape", [(1024,), (4, 512), (2, 3, 256)])
def test_compress_matches_ref(spec, shape, rng):
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    bc_k = ops.compress(x, spec, interpret=True)
    codes_r, exps_r = ref.compress_ref(x, spec)
    assert np.array_equal(np.asarray(bc_k.codes), np.asarray(codes_r))
    assert np.array_equal(np.asarray(bc_k.exps), np.asarray(exps_r))


@pytest.mark.parametrize("spec", KSPECS, ids=lambda s: s.name)
def test_decompress_matches_ref(spec, rng):
    x = jnp.asarray(rng.standard_normal((4, 1024)), jnp.float32)
    bc = F.compress(x, spec)
    y_k = ops.decompress(bc, interpret=True)
    y_r = F.decompress(bc)
    assert np.array_equal(np.asarray(y_k), np.asarray(y_r))


#: basis shapes of the fused contractions: aligned ones, then the solver's
#: awkward ones at small n — m = 101 (GMRES(100), not a multiple of 8), n
#: not a multiple of 128 (one ragged tile) and n < 128 (one chunk narrower
#: than the lanes)
FUSED_SHAPES = [(8, 1024), (16, 2048), (8, 4096), (101, 1000), (101, 100)]


@pytest.mark.parametrize("spec", [KSPECS[0], KSPECS[1], KSPECS[4]],
                         ids=lambda s: s.name)
@pytest.mark.parametrize("mn", FUSED_SHAPES)
def test_matvec_fused(spec, mn, rng):
    m, n = mn
    V = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    x = jnp.asarray(rng.standard_normal(n), jnp.float32)
    bc = ops.compress(V, spec, interpret=True)
    y_k = ops.matvec(bc, x, interpret=True)
    y_r = ref.matvec_ref(bc.codes, bc.exps, jnp.pad(
        x, (0, bc.codes.shape[-2] * spec.bs - n)), spec)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("spec,mn", [
    pytest.param(s, mn, id=s.name if mn == (16, 2048)
                 else f"{s.name}-{mn[0]}x{mn[1]}")
    for mn in [(16, 2048)] + FUSED_SHAPES[3:]
    for s in (KSPECS[0], KSPECS[1], KSPECS[4])])
def test_rmatvec_fused(spec, mn, rng):
    m, n = mn
    V = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    h = jnp.asarray(rng.standard_normal(m), jnp.float32)
    bc = ops.compress(V, spec, interpret=True)
    y_k = ops.rmatvec(bc, h, interpret=True)
    y_r = ref.rmatvec_ref(bc.codes, bc.exps, h, spec)[: n]
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-5, atol=2e-5)


def _primitives(jaxpr) -> set[str]:
    """Names of every primitive in ``jaxpr`` and its sub-jaxprs."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


#: (m, n, bm, bn) of the kernels at explicit small tiles (bs = 8, so a
#: group of 128 * bs = 1024 lanes): two groups a tile (the walk's loop),
#: a ragged last n tile cut inside a chunk (2952 = 2048 + 904), and for
#: m = 300 a ragged last row tile
TILED = [(101, 2952, 101, 2048), (300, 2952, 128, 2048)]


@pytest.mark.parametrize("q", [1, 3])
@pytest.mark.parametrize("op", ["dots", "combine"])
@pytest.mark.parametrize("shape", TILED)
def test_fused_path_follows_q(shape, op, q, rng):
    """One right-hand side and several (block GMRES) both contract on the
    VPU, one decode of each chunk serving every row of ``W`` / ``Y`` (no
    dot in the kernel), and match the float64 contraction of the decoded
    basis.  The basis rows and the contracted vectors have unit norm, as
    GMRES keeps them."""
    spec = F.FrszSpec(bs=8, l=16, dtype=jnp.float32)
    m, n, bm, bn = shape

    def unit_rows(shape):
        x = rng.standard_normal(shape)
        return jnp.asarray(x / np.linalg.norm(x, axis=1, keepdims=True),
                           jnp.float32)

    bc = ops.compress(unit_rows((m, n)), spec, interpret=True)
    codes, exps = ops._basis_2d(bc)
    Vd = np.asarray(F.decompress(bc), np.float64)
    kernel = KD.dots_2d if op == "dots" else KD.combine_2d
    A = unit_rows((q, n if op == "dots" else m))
    run = functools.partial(kernel, codes, exps, spec=spec, bm=bm, bn=bn,
                            interpret=True)
    want = (Vd @ np.asarray(A, np.float64).T if op == "dots"
            else np.asarray(A, np.float64) @ Vd)
    calls = [e for e in jax.make_jaxpr(run)(A).eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    body = _primitives(calls[0].params["jaxpr"])
    assert "dot_general" not in body, sorted(body)
    np.testing.assert_allclose(np.asarray(run(A)), want, rtol=2e-5,
                               atol=2e-5)


#: block exponents at both ends of the range: all-flushed blocks (0, 1),
#: the edge where the largest codes stop underflowing (l - 2, l - 1), the
#: bias, and the top two finite exponents
def _edge_exponents(l):
    return [0, 1, l - 2, l - 1, 127, 253, 254]


def _decoded_by_kernel(codes, exps, spec):
    """``codes (R, n)`` decoded inside a Pallas kernel (interpret mode) by
    the chunk walk that the contractions run."""

    def kernel(c_ref, e_ref, o_ref):
        def visit(carry, lanes, vals, mask):
            o_ref[:, lanes] = vals
            return carry
        KD._walk(c_ref, e_ref, spec, c_ref.shape[1], visit, None)

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(codes.shape, spec.dtype),
        interpret=True)(codes, exps)


@pytest.mark.parametrize("bs", [32, 128])
@pytest.mark.parametrize("l", [8, 16])
def test_scale_decode_bit_identical(l, bs):
    """Every l-bit code under every edge exponent decodes, inside the
    kernel, to the very bits of ``core.frsz2._decode_values`` (bs 32: four
    blocks a chunk; bs 128: one, and for l = 8 a tile narrower than one
    row of block exponents)."""
    spec = F.FrszSpec(bs=bs, l=l, dtype=jnp.float32)
    assert KD._scaled(spec)
    E = np.asarray(_edge_exponents(l), np.int32)
    n = 1 << l
    nb = n // spec.bs
    codes = np.broadcast_to(np.arange(n, dtype=F._code_dtype(l)),
                            (len(E), n))
    # row r, block b: exponent E[(r + b) % 7], so each code meets each one
    exps = E[(np.arange(len(E))[:, None] + np.arange(nb)[None, :]) % len(E)]
    got = _decoded_by_kernel(jnp.asarray(codes), jnp.asarray(exps), spec)
    want = F._decode_values(jnp.asarray(codes),
                            jnp.asarray(np.repeat(exps, spec.bs, axis=1)),
                            spec)
    assert np.array_equal(np.asarray(got).view(np.uint32),
                          np.asarray(want).view(np.uint32))


@pytest.mark.parametrize("l", [8, 16])
@pytest.mark.parametrize("BHkv", [(2, 2, 8), (1, 1, 4), (2, 4, 4)])
def test_decode_attn_kernel(l, BHkv, rng):
    B, Hkv, G = BHkv
    H, D, S = Hkv * G, 128, 512
    spec = F.FrszSpec(bs=D, l=l, dtype=jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    lengths = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
    kbc = ops.compress(k, spec, interpret=True)
    vbc = ops.compress(v, spec, interpret=True)
    out_k = ops.decode_attention(q, kbc, vbc, lengths, interpret=True)
    out_r = ref.decode_attn_ref(
        q, kbc.codes.reshape(B, Hkv, S, -1), kbc.exps,
        vbc.codes.reshape(B, Hkv, S, -1), vbc.exps, lengths, spec)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-3, atol=2e-3)


def test_kernel_fallback_unaligned():
    # unaligned l falls back to the pure-jnp codec transparently
    spec = F.FrszSpec(bs=32, l=21, dtype=jnp.float64)
    x = jnp.asarray(np.linspace(-1, 1, 320), jnp.float64)
    bc = ops.compress(x, spec)
    y = ops.decompress(bc)
    assert np.allclose(np.asarray(y), np.asarray(x), atol=2e-5)


def test_interpret_follows_backend(monkeypatch):
    # compiled on TPU, interpreted elsewhere; an interpret pin on a TPU
    # backend is an error, never a silent mode
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._resolve_interpret(None) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._resolve_interpret(None) is False
    assert ops._resolve_interpret(False) is False
    with pytest.raises(RuntimeError, match="interpret mode"):
        ops._resolve_interpret(True)
    monkeypatch.setattr(ops, "INTERPRET", True)
    with pytest.raises(RuntimeError, match="interpret mode"):
        ops._resolve_interpret(None)
