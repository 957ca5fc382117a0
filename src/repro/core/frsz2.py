"""FRSZ2: block floating-point compression (paper Sec. IV), dtype-generic.

The format groups ``BS`` consecutive values into a block, stores the block's
maximum IEEE exponent ``e_max`` once, and stores each value as an ``l``-bit
code::

    c = [ sign | integer bit | fraction bits ]          (paper Eq. 2)

whose significand is the input significand (explicit leading 1) right-shifted
by ``k = e_max - e``.  Decompression recovers ``k`` with a count-leading-zeros
over the code's significand field and re-packs an IEEE value.

This module is the *pure-jnp reference implementation* ("the math").  It is
dtype-generic (float32 / float64 — float64 requires ``jax.enable_x64``) and
supports arbitrary code lengths ``l`` (including unaligned ones such as the
paper's l=21) and arbitrary block sizes ``BS``.  The Pallas TPU kernels in
``repro.kernels`` implement the aligned fast paths (l in {8, 16, 32},
BS multiple of the 128-lane VREG width) and are validated against this module.

Storage (paper Eq. 3, word size w=4 bytes)::

    ceil(n/BS) * ceil(BS*l/32) * 4   bytes of codes
  + ceil(n/BS) * 4                   bytes of exponents
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import require_codec_dtype

__all__ = [
    "FrszSpec",
    "BlockCompressed",
    "compress",
    "decompress",
    "storage_nbytes",
    "bits_per_value",
    "FRSZ2_32",
    "FRSZ2_21",
    "FRSZ2_16",
    "FRSZ2_8",
]


# ---------------------------------------------------------------------------
# IEEE-754 layout constants per value dtype
# ---------------------------------------------------------------------------

_IEEE = {
    jnp.dtype("float32"): dict(
        uint=jnp.uint32, mant=23, expbits=8, bias=127, width=32),
    jnp.dtype("float64"): dict(
        uint=jnp.uint64, mant=52, expbits=11, bias=1023, width=64),
    jnp.dtype("bfloat16"): dict(
        uint=jnp.uint16, mant=7, expbits=8, bias=127, width=16),
    jnp.dtype("float16"): dict(
        uint=jnp.uint16, mant=10, expbits=5, bias=15, width=16),
}


def _code_dtype(l: int):
    """Smallest unsigned integer dtype that holds an l-bit code."""
    if l <= 8:
        return jnp.uint8
    if l <= 16:
        return jnp.uint16
    if l <= 32:
        return jnp.uint32
    return jnp.uint64


@dataclasses.dataclass(frozen=True)
class FrszSpec:
    """Static description of an FRSZ2 format.

    Attributes:
      bs: block size (values per shared exponent).  Paper: 32 (CUDA warp);
        TPU-native default: 128 (VREG lane count).
      l: bits per compressed value (sign + integer bit + fraction bits).
      dtype: the *arithmetic / value* dtype the codec round-trips.
      rounding: 'truncate' (paper Sec. IV step 5: "cut") or 'nearest'
        (beyond-paper: round-half-up before the cut; strictly more accurate).
      exp_dtype: storage dtype of the per-block exponent.  The paper uses a
        32-bit integer ("frsz2_32 needs 33 bits per value on average").
    """

    bs: int = 128
    l: int = 32
    dtype: Any = jnp.float32
    rounding: str = "truncate"
    exp_dtype: Any = jnp.int32

    def __post_init__(self):
        if self.l < 3:
            raise ValueError("l must be >= 3 (sign + integer bit + >=1 fraction bit)")
        ieee = _IEEE.get(jnp.dtype(self.dtype))
        if ieee is None:
            raise ValueError(f"unsupported value dtype {self.dtype}")
        if self.l > ieee["width"]:
            raise ValueError(f"l={self.l} exceeds dtype width {ieee['width']}")
        if 32 < self.l < 64:
            # the packed layout does 32-bit word arithmetic (a code spans at
            # most two words); the paper's useful range is l <= 32, plus the
            # aligned l = 64 passthrough.
            raise ValueError("unaligned l in (32, 64) is unsupported")
        if self.rounding not in ("truncate", "nearest"):
            raise ValueError(f"unknown rounding {self.rounding!r}")
        if self.bs < 1:
            raise ValueError("bs must be positive")
        require_codec_dtype(self.dtype)

    # -- derived ------------------------------------------------------------
    @property
    def ieee(self):
        return _IEEE[jnp.dtype(self.dtype)]

    @property
    def aligned(self) -> bool:
        """Aligned codes can be stored one-per-integer without bit packing."""
        return self.l in (8, 16, 32, 64)

    @property
    def words_per_block(self) -> int:
        """uint32 words of code storage per block (packed layout, Eq. 3)."""
        return -(-self.bs * self.l // 32)

    @property
    def name(self) -> str:
        return f"frsz2_{self.l}(bs={self.bs},{jnp.dtype(self.dtype).name})"


FRSZ2_32 = FrszSpec(bs=128, l=32)
FRSZ2_21 = FrszSpec(bs=128, l=21)
FRSZ2_16 = FrszSpec(bs=128, l=16)
FRSZ2_8 = FrszSpec(bs=128, l=8)


# ---------------------------------------------------------------------------
# Compressed container (a pytree)
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BlockCompressed:
    """FRSZ2-compressed array.

    The array is compressed along its *last* axis; leading axes are batch.
    ``codes`` has shape ``batch + (nblocks, bs)`` for aligned specs or
    ``batch + (nblocks, words_per_block)`` (uint32) for packed specs.
    ``exps`` has shape ``batch + (nblocks,)``.
    ``n`` is the logical length of the last axis (may not divide bs; the
    tail block is zero-padded — zero codes decompress to exact zeros).
    """

    codes: jax.Array
    exps: jax.Array
    n: int
    spec: FrszSpec

    # -- pytree protocol -----------------------------------------------------
    def tree_flatten(self):
        return (self.codes, self.exps), (self.n, self.spec)

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, exps = children
        n, spec = aux
        return cls(codes=codes, exps=exps, n=n, spec=spec)

    # -- convenience ----------------------------------------------------------
    @property
    def shape(self):
        return tuple(self.exps.shape[:-1]) + (self.n,)

    @property
    def nblocks(self) -> int:
        return self.exps.shape[-1]

    def nbytes(self) -> int:
        return int(np.prod(self.codes.shape)) * self.codes.dtype.itemsize + int(
            np.prod(self.exps.shape)
        ) * self.exps.dtype.itemsize

    def decompress(self) -> jax.Array:
        return decompress(self)


# ---------------------------------------------------------------------------
# Bit helpers
# ---------------------------------------------------------------------------


def _clz(x: jax.Array) -> jax.Array:
    """Count leading zeros; jax.lax.clz is a primitive on all backends."""
    return jax.lax.clz(x)


def _field_clz(csig: jax.Array, field_bits: int) -> jax.Array:
    """Leading zeros of ``csig`` interpreted as a ``field_bits``-wide field."""
    width = jnp.iinfo(csig.dtype).bits
    return _clz(csig) - (width - field_bits)


# ---------------------------------------------------------------------------
# Compression (paper Sec. IV-A, 6 steps)
# ---------------------------------------------------------------------------


def _split_ieee(x: jax.Array, spec: FrszSpec):
    """Steps 1-2: extract sign, biased exponent, significand (explicit 1)."""
    ieee = spec.ieee
    u = jax.lax.bitcast_convert_type(x.astype(spec.dtype), ieee["uint"])
    one = jnp.asarray(1, ieee["uint"])
    sign = (u >> (ieee["mant"] + ieee["expbits"])) & one
    e = (u >> ieee["mant"]) & jnp.asarray((1 << ieee["expbits"]) - 1, ieee["uint"])
    m = u & jnp.asarray((1 << ieee["mant"]) - 1, ieee["uint"])
    # Subnormals (e == 0) are treated as zero: their magnitude is < 2^(1-bias),
    # irrelevant for normalized Krylov data (paper implicitly does the same —
    # the leading-1 trick requires normal numbers).
    normal = e > 0
    sig = jnp.where(normal, m | (one << ieee["mant"]), jnp.zeros_like(m))
    e = jnp.where(normal, e, jnp.zeros_like(e))
    return sign, e, sig


def _encode_block(sign, e, sig, emax, spec: FrszSpec):
    """Steps 3-5 for ``(..., bs)`` blocks sharing ``emax (...)``."""
    return _encode_values(sign, e, sig, emax[..., None], spec)


def _encode_values(sign, e, sig, emax, spec: FrszSpec):
    """Steps 3-5: normalize to e_max, prepend sign, cut to l bits.

    ``emax`` holds each value's block exponent, broadcast to ``e``'s shape
    (the Pallas kernels pass per-lane exponents of a 2-D tile).
    """
    ieee = spec.ieee
    ucode = ieee["uint"]
    mant = ieee["mant"]
    l = spec.l
    # zeros have e=0 -> huge k -> code 0
    k = emax.astype(jnp.int32) - e.astype(jnp.int32)
    # target: fixed point with 1 integer bit + (l-2) fraction bits
    # c_sig = sig * 2^(l-2) / 2^(mant+k)  ->  shift = mant - (l-2) + k
    shift = mant - (l - 2) + k
    width = ieee["width"]
    # right shift (possibly negative -> left shift).  Guard shift >= width.
    rs = jnp.clip(shift, 0, width - 1)
    ls = jnp.clip(-shift, 0, width - 1)
    big = shift >= width
    if spec.rounding == "nearest":
        # round-half-up prior to the cut; clamp on overflow of the field
        half = jnp.where(
            rs > 0,
            jnp.asarray(1, ucode) << jnp.maximum(rs - 1, 0).astype(ucode),
            jnp.asarray(0, ucode),
        )
        sig_r = sig + jnp.where(shift > 0, half, jnp.zeros_like(half))
    else:
        sig_r = sig
    csig = jnp.where(
        shift >= 0,
        sig_r >> rs.astype(ucode),
        sig_r << ls.astype(ucode),
    )
    csig = jnp.where(big, jnp.zeros_like(csig), csig)
    field_max = jnp.asarray((1 << (l - 1)) - 1, ucode)
    # overflow clamp (nearest-rounding edge); a select, since Mosaic has no
    # unsigned min
    csig = jnp.where(csig > field_max, field_max, csig)
    c = (sign << (l - 1)) | csig
    return c


def compress(x: jax.Array, spec: FrszSpec = FRSZ2_32) -> BlockCompressed:
    """Compress ``x`` along its last axis into FRSZ2 blocks.

    Works for any leading batch shape.  The tail block is zero padded.
    """
    x = jnp.asarray(x, spec.dtype)
    *batch, n = x.shape
    nb = -(-n // spec.bs)
    pad = nb * spec.bs - n
    if pad:
        x = jnp.pad(x, [(0, 0)] * len(batch) + [(0, pad)])
    xb = x.reshape(*batch, nb, spec.bs)

    sign, e, sig = _split_ieee(xb, spec)
    emax = e.max(axis=-1)  # step 1: block max exponent
    c = _encode_block(sign, e, sig, emax, spec)  # steps 2-5

    code_dt = _code_dtype(spec.l)
    codes = (c.astype(code_dt) if spec.aligned
             else _pack_bits(c.astype(jnp.uint64), spec))
    return BlockCompressed(
        codes=codes, exps=emax.astype(spec.exp_dtype), n=n, spec=spec
    )


# ---------------------------------------------------------------------------
# Decompression (paper Sec. IV-B, 4 steps)
# ---------------------------------------------------------------------------


def _decode_block(c: jax.Array, emax: jax.Array, spec: FrszSpec) -> jax.Array:
    """``(..., bs)`` codes sharing ``emax (...)`` -> values."""
    return _decode_values(c, emax[..., None], spec)


def _decode_values(c: jax.Array, emax: jax.Array, spec: FrszSpec) -> jax.Array:
    """Codes -> values, with ``emax`` each code's block exponent broadcast
    to ``c``'s shape (the Pallas kernels pass per-lane exponents)."""
    ieee = spec.ieee
    ucode = ieee["uint"]
    mant, expbits, l = ieee["mant"], ieee["expbits"], spec.l
    c = c.astype(ucode)
    one = jnp.asarray(1, ucode)
    sign = (c >> (l - 1)) & one
    csig = c & jnp.asarray((1 << (l - 1)) - 1, ucode)
    zero = csig == 0
    # step 2: k = number of prefixed zeros in the (l-1)-wide field
    k = _field_clz(csig, l - 1).astype(jnp.int32)
    k = jnp.where(zero, jnp.zeros_like(k), k)
    e = emax.astype(jnp.int32) - k
    # step 3: drop the leading 1; nf = l-2-k fraction bits remain
    nf = l - 2 - k
    frac = csig ^ jnp.where(
        zero, jnp.zeros_like(csig), one << jnp.maximum(nf, 0).astype(ucode))
    d = mant - nf  # left shift if positive, right if negative
    width = ieee["width"]
    m = jnp.where(
        d >= 0,
        frac << jnp.clip(d, 0, width - 1).astype(ucode),
        frac >> jnp.clip(-d, 0, width - 1).astype(ucode),
    )
    e = jnp.where(zero | (e <= 0), jnp.zeros_like(e), e)  # flush to (signed) zero
    m = jnp.where(e == 0, jnp.zeros_like(m), m)
    u = (sign << (mant + expbits)) | (e.astype(ucode) << mant) | m
    return jax.lax.bitcast_convert_type(u, spec.dtype)


def decompress(bc: BlockCompressed) -> jax.Array:
    """Inverse of :func:`compress`; returns the logical ``batch + (n,)`` array."""
    spec = bc.spec
    c = bc.codes if spec.aligned else _unpack_bits(bc.codes, spec)
    x = _decode_block(c, bc.exps, spec)
    *batch, nb, bs = x.shape
    x = x.reshape(*batch, nb * bs)
    return x[..., : bc.n]


# ---------------------------------------------------------------------------
# Generic-l bit packing (ref-only; kernels use aligned l)
# ---------------------------------------------------------------------------


def _pack_bits(c: jax.Array, spec: FrszSpec) -> jax.Array:
    """Pack ``batch + (nb, bs)`` l-bit codes into ``batch + (nb, W)`` uint32.

    Pure 32-bit arithmetic (works without ``jax_enable_x64``): each code
    straddles at most two words; the high spill is ``c >> (32 - b0)``.
    """
    l, bs, W = spec.l, spec.bs, spec.words_per_block
    *batch, nb, _ = c.shape
    c = c.astype(jnp.uint32)
    j = np.arange(bs)
    off = j * l
    w0 = jnp.asarray(off // 32)
    b0 = off % 32
    b0j = jnp.asarray(b0, jnp.uint32)
    lo = c << b0j  # uint32 shift naturally drops the spilled high bits
    # guard shift-by-32 (undefined): where b0 == 0 there is no spill
    hi_shift = jnp.asarray(np.clip(32 - b0, 0, 31), jnp.uint32)
    hi = jnp.where(jnp.asarray(b0 == 0), jnp.zeros_like(c), c >> hi_shift)
    words = jnp.zeros((*batch, nb, W + 1), jnp.uint32)
    # bit-fields never overlap, so add == or; the +1 word catches the last spill
    words = words.at[..., w0].add(lo, mode="promise_in_bounds")
    words = words.at[..., w0 + 1].add(hi, mode="promise_in_bounds")
    return words[..., :W]


def _unpack_bits(words: jax.Array, spec: FrszSpec) -> jax.Array:
    """Inverse of :func:`_pack_bits` -> ``batch + (nb, bs)`` uint32 codes."""
    l, bs, W = spec.l, spec.bs, spec.words_per_block
    j = np.arange(bs)
    off = j * l
    w0 = off // 32
    b0 = off % 32
    wpad = jnp.concatenate(
        [words, jnp.zeros(words.shape[:-1] + (1,), words.dtype)], axis=-1
    )
    lo = wpad[..., w0] >> jnp.asarray(b0, jnp.uint32)
    hi_shift = jnp.asarray(np.clip(32 - b0, 0, 31), jnp.uint32)
    hi = jnp.where(
        jnp.asarray(b0 == 0),
        jnp.zeros_like(lo),
        wpad[..., w0 + 1] << hi_shift,
    )
    mask = jnp.uint32((1 << l) - 1) if l < 32 else jnp.uint32(0xFFFFFFFF)
    return (lo | hi) & mask


# ---------------------------------------------------------------------------
# Storage accounting (paper Eq. 3)
# ---------------------------------------------------------------------------


def storage_nbytes(n: int, spec: FrszSpec) -> int:
    """Bytes to store ``n`` values, per paper Eq. 3 (4-byte words)."""
    nb = -(-n // spec.bs)
    return nb * spec.words_per_block * 4 + nb * 4


def bits_per_value(spec: FrszSpec) -> float:
    """Average bits per value including the externalized exponent."""
    return (spec.words_per_block * 32 + 32) / spec.bs
