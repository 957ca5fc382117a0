"""Codec properties: roundtrip error bounds, idempotence, storage (Eq. 3)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import frsz2 as F

SPECS = [
    F.FrszSpec(bs=32, l=32, dtype=jnp.float64),     # the paper's frsz2_32
    F.FrszSpec(bs=32, l=21, dtype=jnp.float64),     # unaligned l
    F.FrszSpec(bs=32, l=16, dtype=jnp.float64),
    F.FrszSpec(bs=128, l=32, dtype=jnp.float32),    # TPU-native
    F.FrszSpec(bs=128, l=16, dtype=jnp.float32),
    F.FrszSpec(bs=128, l=8, dtype=jnp.float32),
    F.FrszSpec(bs=8, l=16, dtype=jnp.float32),
]


def _max_block_error(x, spec):
    """Per-block worst-case absolute error bound for truncation coding:
    values keep l-2 significant bits below the block max exponent."""
    xb = np.asarray(x).reshape(-1, spec.bs)
    mags = np.abs(xb)
    emax = np.where(mags.max(1) > 0,
                    np.floor(np.log2(mags.max(1) + 1e-300)), 0)
    return 2.0 ** (emax - (spec.l - 2) + 1)        # +1: conservative


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_roundtrip_bound(spec, rng):
    n = spec.bs * 7 + 3                             # ragged tail
    x = rng.standard_normal(n) * 10.0 ** rng.integers(-3, 3, n)
    x = jnp.asarray(x, spec.dtype)
    y = np.asarray(F.decompress(F.compress(x, spec)))
    bound = np.repeat(_max_block_error(
        np.pad(np.asarray(x), (0, spec.bs * 8 - n)), spec), spec.bs)[:n]
    assert np.all(np.abs(y - np.asarray(x)) <= bound + 1e-300)


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_idempotent(spec, rng):
    x = jnp.asarray(rng.standard_normal(spec.bs * 4), spec.dtype)
    bc1 = F.compress(x, spec)
    y = F.decompress(bc1)
    bc2 = F.compress(y, spec)
    assert np.array_equal(np.asarray(bc1.codes), np.asarray(bc2.codes))
    assert np.array_equal(np.asarray(bc1.exps), np.asarray(bc2.exps))
    assert np.array_equal(np.asarray(F.decompress(bc2)), np.asarray(y))


def test_zeros_and_signs(rng):
    spec = F.FrszSpec(bs=32, l=16, dtype=jnp.float32)
    x = jnp.asarray([0.0, -0.0, 1.0, -1.0, 0.5, -0.5] + [0.0] * 26,
                    jnp.float32)
    y = np.asarray(F.decompress(F.compress(x, spec)))
    assert y[0] == 0 and y[1] == 0
    np.testing.assert_allclose(y[2:6], [1.0, -1.0, 0.5, -0.5])


def test_exact_for_block_aligned_powers(rng):
    # values whose significands fit in l-2 bits at the shared exponent
    spec = F.FrszSpec(bs=8, l=16, dtype=jnp.float32)
    base = np.asarray([1.0, 0.5, 0.25, 1.75, 1.5, 0.75, 1.25, 0.875])
    y = np.asarray(F.decompress(F.compress(jnp.asarray(base, jnp.float32),
                                           spec)))
    np.testing.assert_array_equal(y, base)


def test_l64_aligned_passthrough(rng):
    spec = F.FrszSpec(bs=32, l=64, dtype=jnp.float64)
    x = jnp.asarray(rng.standard_normal(128), jnp.float64)
    y = np.asarray(F.decompress(F.compress(x, spec)))
    xb = np.asarray(x).reshape(-1, 32)
    scale = np.abs(xb).max(1, keepdims=True)
    assert (np.abs(y.reshape(-1, 32) - xb) / scale).max() <= 2.0 ** -61


def test_unaligned_wide_l_rejected():
    with pytest.raises(ValueError):
        F.FrszSpec(bs=32, l=48, dtype=jnp.float64)


@given(st.integers(3, 32), st.integers(1, 6), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=30, deadline=None)
def test_property_roundtrip_f64(l, bs_pow, seed):
    spec = F.FrszSpec(bs=2 ** bs_pow, l=l, dtype=jnp.float64)
    rng = np.random.default_rng(seed)
    n = spec.bs * 3
    x = jnp.asarray(rng.standard_normal(n), jnp.float64)
    y = np.asarray(F.decompress(F.compress(x, spec)))
    # relative error vs the block max: at most 2^-(l-3)
    xb = np.asarray(x).reshape(-1, spec.bs)
    scale = np.abs(xb).max(1, keepdims=True)
    err = np.abs(y.reshape(-1, spec.bs) - xb) / np.maximum(scale, 1e-300)
    assert err.max() <= 2.0 ** -(l - 3)


def test_rounding_nearest_beats_truncate(rng):
    x = jnp.asarray(rng.standard_normal(128 * 16), jnp.float32)
    t = F.FrszSpec(bs=128, l=16, dtype=jnp.float32, rounding="truncate")
    r = F.FrszSpec(bs=128, l=16, dtype=jnp.float32, rounding="nearest")
    et = np.abs(np.asarray(F.decompress(F.compress(x, t))) - np.asarray(x))
    er = np.abs(np.asarray(F.decompress(F.compress(x, r))) - np.asarray(x))
    assert er.mean() < et.mean()                     # RNE strictly better
    # and truncation biases toward zero; RNE is (near) unbiased
    xt = np.asarray(F.decompress(F.compress(x, t)))
    assert np.all(np.abs(xt) <= np.abs(np.asarray(x)) + 1e-30)


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.name)
def test_storage_eq3(spec):
    n = spec.bs * 5 + 1
    bc = F.compress(jnp.zeros((n,), spec.dtype), spec)
    nb = -(-n // spec.bs)
    # paper Eq. 3 with 4-byte words
    expect = nb * spec.words_per_block * 4 + nb * 4
    assert F.storage_nbytes(n, spec) == expect
    if not spec.aligned:
        assert bc.codes.shape[-1] == spec.words_per_block


def test_pack_unpack_arbitrary_l(rng):
    spec = F.FrszSpec(bs=32, l=21, dtype=jnp.float64)
    c = jnp.asarray(rng.integers(0, 2 ** 21, (4, spec.bs)), jnp.uint64)
    words = F._pack_bits(c, spec)
    back = F._unpack_bits(words, spec)
    assert np.array_equal(np.asarray(back), np.asarray(c, np.uint32))


def test_bits_per_value_paper_claim():
    # paper Sec. IV-C: frsz2_32 with BS=32 averages 33 bits/value
    assert F.bits_per_value(F.FrszSpec(bs=32, l=32, dtype=jnp.float64)) == 33.0


def test_f64_codec_refused_on_tpu(monkeypatch):
    # XLA:TPU has no f64 -> u64 bitcast-convert: a float64 FRSZ2 format
    # raises there instead of silently running in float32
    import jax

    from repro import runtime
    from repro.core.accessor import format_by_name

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="bitcast-convert f64->u64"):
        F.FrszSpec(bs=32, l=32, dtype=jnp.float64)
    with pytest.raises(ValueError, match="UNIMPLEMENTED"):
        format_by_name("frsz2_32", arith_dtype=jnp.float64)
    assert runtime.arith_dtype() == jnp.float32
    assert format_by_name("frsz2_16").spec.dtype == jnp.float32
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert runtime.arith_dtype() == jnp.float64
