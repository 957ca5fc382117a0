"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import frsz2 as F
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


@pytest.mark.parametrize("spec", [KSPECS[0], KSPECS[1]],
                         ids=lambda s: s.name)
@pytest.mark.parametrize("mn", [(8, 1024), (16, 2048), (8, 4096)])
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


@pytest.mark.parametrize("spec", [KSPECS[0], KSPECS[1]],
                         ids=lambda s: s.name)
def test_rmatvec_fused(spec, rng):
    m, n = 16, 2048
    V = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    h = jnp.asarray(rng.standard_normal(m), jnp.float32)
    bc = ops.compress(V, spec, interpret=True)
    y_k = ops.rmatvec(bc, h, interpret=True)
    y_r = ref.rmatvec_ref(bc.codes, bc.exps, h, spec)[: n]
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-5, atol=2e-5)


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
