"""Fused decompress + contraction Pallas kernels.

These are the CB-GMRES hot loops (paper Fig. 1, steps 4 and 5): the Krylov
basis ``V`` (``M`` rows of length ``N``, FRSZ2-compressed) is *read* twice
per iteration — once for the dots ``h = V w`` and once for the update
``w -= V^T h``.  Fusing decompression into the contraction is the TPU
analogue of the paper's Accessor read path: codes go HBM -> VMEM -> VREG
and are expanded in-register, with no uncompressed HBM round-trip.

Layouts (wrappers in ops.py produce them):
  codes: (M, N)  one aligned code per element (uint8/16/32)
  exps:  (M, N // bs) int32
  dots:     W (q, N)  -> Y (M, q)   = decompress(V) @ W^T
  combine:  Y (q, M)  -> out (q, N) = Y @ decompress(V)

Both contract on the VPU in f32.  The code tile is walked 128 lanes at a
time (:func:`_walk`); each chunk is decoded into registers once and
multiplied by each of the ``q`` rows of ``W`` / ``Y`` (``q = 1`` for scalar
GMRES, ``q = p`` right-hand sides for block GMRES) — the dots add ``vals *
w`` into a ``(bm, 128)`` accumulator per row, reduced across lanes once per
tile, the combine reduces ``y * vals`` over the basis rows into the
chunk's ``(1, 128)`` slice of each output row.  Nothing wider than a chunk
is decoded at once, and no MXU pass rounds an operand below f32.

Decode by integer scaling: a code's value is ``(-1)^sign * csig *
2^(emax - bias - (l - 2))`` with ``csig`` its ``(l - 1)``-bit field, so a
chunk decodes as mask, int -> float, two power-of-two scalings and the sign
bit (:func:`scale_decode`), bit-identical to ``core.frsz2._decode_values``.
That needs the field to convert exactly (``l - 1`` at most the value
dtype's significand bits); a wider field (float32 ``l = 32``) keeps the
bit decode.

TPU tiling: the exponent tile ``(bm, bn / bs)`` must be lane-aligned, so a
multi-tile reduction axis takes ``bn`` a multiple of ``128 * bs``.  Ragged
edge tiles are masked in-kernel, and only the last tile of an axis is
(its valid width is static), so no operand is ever padded (a pad would copy
the whole basis per call).

Reduction accuracy: when the contraction axis spans multiple grid tiles,
partial results are combined with **Kahan compensated summation** (a
compensation term in VMEM scratch) instead of plain ``+=`` — sequential
f32 tile accumulation loses ~2 bits per doubling of tile count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import frsz2 as F
from repro.core.frsz2 import _decode_block, _decode_values
from repro.kernels.frsz2_kernel import LANES, lane_exponents


def _decode_tile(c_tile, e_tile, spec: F.FrszSpec):
    """(bm, bn) codes + (bm, bn/bs) exps -> (bm, bn) values (vectorized;
    the attention kernel uses it)."""
    e_lanes = jnp.repeat(e_tile, spec.bs, axis=1) if spec.bs > 1 else e_tile
    return _decode_block(c_tile[..., None], e_lanes, spec)[..., 0]


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _scaled(spec: F.FrszSpec) -> bool:
    """Whether codes decode by integer scaling: the ``(l - 1)``-bit field
    fits the value dtype's fraction bits."""
    return spec.l - 1 <= spec.ieee["mant"]


def block_scales(e: jax.Array, spec: F.FrszSpec) -> jax.Array:
    """Block exponents -> ``2^(emax - bias)`` in the value dtype (0 where
    ``emax == 0``, whose codes all decode to zero)."""
    ieee = spec.ieee
    return jax.lax.bitcast_convert_type(
        e.astype(ieee["uint"]) << ieee["mant"], spec.dtype)


def scale_decode(c: jax.Array, s: jax.Array, spec: F.FrszSpec) -> jax.Array:
    """Codes + each code's block scale (:func:`block_scales`) -> values.

    The field lands in the fraction bits of ``2^(mant - l + 2)``, and
    subtracting that power again leaves ``csig * 2^-(l-2)`` exactly (zero
    for a zero field); one multiply by the block scale keeps it exact
    wherever the value is normal.  A value below the smallest normal has
    ``emax - k <= 0`` (``k`` the field's leading zeros), which is exactly
    where ``_decode_values`` flushes to a signed zero.  The product
    flushes there too where f32 arithmetic flushes subnormal results to
    zero: XLA's CPU backend does, which the tests run, and so does a TPU
    v5e (every 8- and 16-bit code at the edge exponents, compiled and
    compared on the chip).  The sign bit then gives the zero its sign.
    With that, bit-identical to ``_decode_values`` for block exponents up
    to 254 (every finite input).
    """
    ieee = spec.ieee
    ucode, mant, width, l = ieee["uint"], ieee["mant"], ieee["width"], spec.l
    cu = c.astype(ucode)
    lead = (ieee["bias"] + mant - (l - 2)) << mant
    fixed = jax.lax.bitcast_convert_type(
        (cu & ((1 << (l - 1)) - 1)) | lead, spec.dtype) - 2.0 ** (mant - l + 2)
    sign = (cu >> (l - 1)) << (width - 1)
    return jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(fixed * s, ucode) | sign, spec.dtype)


def _block_factors(e, spec: F.FrszSpec):
    """What a chunk decode takes per block: scales, or the raw exponents
    where the bit decode stays."""
    return block_scales(e, spec) if _scaled(spec) else e


def _decode_chunk(c, f, spec: F.FrszSpec):
    """Codes + per-lane block factors (:func:`_block_factors`) -> values."""
    if _scaled(spec):
        return scale_decode(c, f, spec)
    return _decode_values(c, f, spec)


def _spread(f, bs: int, k: int, width: int, block):
    """Per-lane block factors of the ``width``-lane chunk at lane ``k`` of
    a tile whose block factors are ``f``: one lane gather from the 128-lane
    row of ``f`` that holds them (``block``: each lane's block within the
    chunk), or, where ``f`` has no whole row there, single-lane broadcasts
    (:func:`lane_exponents`)."""
    first = k // bs
    row = first // LANES * LANES
    if f.shape[1] >= row + LANES and width == LANES:
        return jnp.take_along_axis(f[:, row:row + LANES], first - row + block,
                                   axis=1, mode="promise_in_bounds")
    return lane_exponents(f, bs, first, width)


def _lane_blocks(rows: int, bs: int):
    """Each lane's block within a 128-lane chunk."""
    return jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1) // bs


def _below(shape, dim: int, size: int):
    """Mask of the tile positions along ``dim`` before ``size``."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim) < size


def _walk(c_ref, e_ref, spec: F.FrszSpec, valid: int, visit, carry):
    """Decode the first ``valid`` lanes of a code tile 128 at a time.

    ``carry = visit(carry, lanes, vals, mask)`` sees each chunk: its lane
    slice of the tile, its decoded ``(rows, width)`` values, and for the
    chunk that ``valid`` cuts the mask of its lanes before the cut (else
    ``None``); chunks past ``valid`` are skipped.  Whole groups of
    ``128 * bs`` lanes, whose block factors fill one 128-lane row, run in a
    ``fori_loop`` over aligned dynamic slices; the rest is unrolled.
    """
    bs = spec.bs
    group = LANES * bs
    width = c_ref.shape[1]
    groups = valid // group if width % group == 0 else 0
    block = _lane_blocks(c_ref.shape[0], bs)
    start = 0
    if groups > 1:
        def body(g, carry):
            f = _block_factors(
                e_ref[:, pl.ds(pl.multiple_of(g * LANES, LANES), LANES)], spec)
            base = pl.multiple_of(g * group, group)
            for j in range(bs):
                lanes = pl.ds(base + j * LANES, LANES)
                vals = _decode_chunk(c_ref[:, lanes],
                                     _spread(f, bs, j * LANES, LANES, block),
                                     spec)
                carry = visit(carry, lanes, vals, None)
            return carry

        carry = jax.lax.fori_loop(0, groups, body, carry)
        start = groups * group
    if start < valid:
        f = _block_factors(e_ref[:, start // bs:], spec)
        for k in range(start, valid, LANES):
            w = min(LANES, width - k)
            mask = _below((1, w), 1, valid - k) if valid - k < w else None
            vals = _decode_chunk(c_ref[:, k:k + w],
                                 _spread(f, bs, k - start, w, block), spec)
            carry = visit(carry, slice(k, k + w), vals, mask)
    return carry


# ---------------------------------------------------------------------------
# tiles
# ---------------------------------------------------------------------------


def _kahan_accumulate(o_ref, comp_ref, part, k):
    """o += part with a compensated carry; init both refs at tile k == 0."""

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    y = part.astype(o_ref.dtype) - comp_ref[...]
    s = o_ref[...] + y
    comp_ref[...] = (s - o_ref[...]) - y
    o_ref[...] = s


def _by_tile(idx, size: int, tile: int, run):
    """``run(valid)`` for grid tile ``idx`` of an axis of ``size`` cut in
    ``tile``s: ``valid`` is the tile's static in-bounds width, below
    ``tile`` on the ragged last tile only."""
    count = pl.cdiv(size, tile)
    last = size - (count - 1) * tile
    if last == tile:
        run(tile)
        return
    pl.when(idx < count - 1)(lambda: run(tile))
    pl.when(idx == count - 1)(lambda: run(last))


# ---------------------------------------------------------------------------
# Y (M, q) = decompress(V) @ W (q, N)^T
# ---------------------------------------------------------------------------


def _dots_kernel(c_ref, e_ref, w_ref, o_ref, comp_ref, *, spec: F.FrszSpec,
                 n: int, bn: int):
    k = pl.program_id(1)
    bm, q = o_ref.shape

    def tile(valid):
        def visit(acc, lanes, vals, mask):
            sums, tails = acc
            sums, tails = list(sums), list(tails)
            for r in range(q):
                prod = vals * w_ref[r:r + 1, lanes].astype(vals.dtype)
                if mask is not None:
                    prod = jnp.where(mask, prod, 0)
                if prod.shape[1] == LANES:
                    sums[r] = sums[r] + prod
                else:
                    tails[r] = tails[r] + prod.sum(axis=1, keepdims=True)
            return tuple(sums), tuple(tails)

        zero = ((jnp.zeros((bm, LANES), spec.dtype),) * q,
                (jnp.zeros((bm, 1), spec.dtype),) * q)
        sums, tails = _walk(c_ref, e_ref, spec, valid, visit, zero)
        cols = [s.sum(axis=1, keepdims=True) + t for s, t in zip(sums, tails)]
        part = cols[0]
        if q > 1:                 # column r of the (bm, q) partial
            col = jax.lax.broadcasted_iota(jnp.int32, (bm, q), 1)
            part = sum(jnp.where(col == r, c, 0) for r, c in enumerate(cols))
        _kahan_accumulate(o_ref, comp_ref, part, k)

    _by_tile(k, n, bn, tile)


def dots_2d(codes, exps, W, spec: F.FrszSpec, *, bm: int, bn: int,
            interpret: bool = False):
    """codes (M, N), exps (M, N/bs), W (q, N) -> Y (M, q).

    Row tiles are independent (a ragged last one only writes rows that
    exist); the N reduction is innermost and Kahan-compensated.
    """
    m, n = codes.shape
    q = W.shape[0]
    eb = exps.shape[1] if bn == n else bn // spec.bs
    return pl.pallas_call(
        functools.partial(_dots_kernel, spec=spec, n=n, bn=bn),
        grid=(pl.cdiv(m, bm), pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, k: (i, k)),
            pl.BlockSpec((bm, eb), lambda i, k: (i, k)),
            pl.BlockSpec((q, bn), lambda i, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((bm, q), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, q), spec.dtype),
        scratch_shapes=[pltpu.VMEM((bm, q), spec.dtype)],
        interpret=interpret,
    )(codes, exps, W)


# ---------------------------------------------------------------------------
# out (q, N) = Y (q, M) @ decompress(V)
# ---------------------------------------------------------------------------


def _combine_kernel(c_ref, e_ref, y_ref, o_ref, comp_ref, part_ref, *,
                    spec: F.FrszSpec, m: int, bm: int, n: int, bn: int):
    j, k = pl.program_id(0), pl.program_id(1)
    q = o_ref.shape[0]
    # one row tile holds the whole M reduction: chunks sum straight into
    # the output, with nothing to carry across tiles
    whole = pl.cdiv(m, bm) == 1
    out = o_ref if whole else part_ref

    def tile(valid, rows):
        ys = [jnp.broadcast_to(y_ref[:, r:r + 1].astype(spec.dtype),
                               (bm, LANES)) for r in range(q)]
        keep = _below((bm, 1), 0, rows) if rows < bm else None

        def visit(carry, lanes, vals, _mask):
            # lanes past n only reach output columns that are not written
            for r in range(q):
                prod = ys[r][:, :vals.shape[1]] * vals
                if keep is not None:  # ragged last row tile
                    prod = jnp.where(keep, prod, 0)
                out[r:r + 1, lanes] = prod.sum(axis=0, keepdims=True)
            return carry

        _walk(c_ref, e_ref, spec, valid, visit, None)
        if not whole:
            _kahan_accumulate(o_ref, comp_ref, part_ref[...], k)

    _by_tile(j, n, bn,
             lambda valid: _by_tile(k, m, bm, lambda rows: tile(valid, rows)))


def combine_2d(codes, exps, Y, spec: F.FrszSpec, *, bm: int, bn: int,
               interpret: bool = False):
    """codes (M, N), exps (M, N/bs), Y (q, M) -> out (q, N).

    Grid iterates N-tiles in the *outer* loop and M-tiles inner, so each
    output tile is finalized once (the M reduction is innermost); a ragged
    last N tile only writes columns that exist.  ``Y`` enters the kernel
    as ``(M, q)`` columns, each broadcast along lanes.
    """
    m, n = codes.shape
    q = Y.shape[0]
    eb = exps.shape[1] if bn == n else bn // spec.bs
    return pl.pallas_call(
        functools.partial(_combine_kernel, spec=spec, m=m, bm=bm, n=n, bn=bn),
        grid=(pl.cdiv(n, bn), pl.cdiv(m, bm)),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda j, k: (k, j)),
            pl.BlockSpec((bm, eb), lambda j, k: (k, j)),
            pl.BlockSpec((bm, q), lambda j, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((q, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((q, n), spec.dtype),
        scratch_shapes=[pltpu.VMEM((q, bn), spec.dtype)] * 2,
        interpret=interpret,
    )(codes, exps, Y.T)
