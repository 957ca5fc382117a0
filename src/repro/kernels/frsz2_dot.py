"""Fused decompress + contraction Pallas kernels.

These are the CB-GMRES hot loops (paper Fig. 1, steps 4 and 5): the Krylov
basis ``V`` (``M`` rows of length ``N``, FRSZ2-compressed) is *read* twice
per iteration — once for the dots ``h = V w`` and once for the update
``w -= V^T h``.  Fusing decompression into the contraction is the TPU
analogue of the paper's Accessor read path: codes go HBM -> VMEM -> VREG,
are expanded in-register, and feed the MXU without an uncompressed HBM
round-trip.  One kernel pair serves both solvers: ``q = 1`` is scalar
GMRES, ``q = p`` the block-GMRES contractions over ``p`` right-hand sides
(one decode of each tile serves the whole block).

Layouts (wrappers in ops.py produce them):
  codes: (M, N)  one aligned code per element (uint8/16/32)
  exps:  (M, N // bs) int32
  dots:     W (q, N)  -> Y (M, q)   = decompress(V) @ W^T
  combine:  Y (q, M)  -> out (q, N) = Y @ decompress(V)

TPU tiling: the exponent tile ``(bm, bn / bs)`` must be lane-aligned, so a
multi-tile reduction axis takes ``bn`` a multiple of ``128 * bs``.  Tiles
decode 128 lanes at a time (:func:`decode_lanes`), and ragged edge tiles
are masked in-kernel, so no operand is ever padded (a pad would copy the
whole basis per call).

Reduction accuracy: when the contraction axis spans multiple grid tiles,
partial results are combined with **Kahan compensated summation** (a
compensation term in VMEM scratch) instead of plain ``+=`` — sequential
f32 tile accumulation loses ~2 bits per doubling of tile count.  The
contractions run at ``Precision.HIGHEST``: the MXU's default f32 pass
rounds operands to bfloat16, which would cap GMRES orthogonality near
2^-8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import frsz2 as F
from repro.core.accessor import HIGHEST as _HIGHEST
from repro.core.frsz2 import _decode_block, _decode_values
from repro.kernels.frsz2_kernel import LANES, lane_exponents


def _decode_tile(c_tile, e_tile, spec: F.FrszSpec):
    """(bm, bn) codes + (bm, bn/bs) exps -> (bm, bn) values (vectorized;
    the attention kernel uses it)."""
    e_lanes = jnp.repeat(e_tile, spec.bs, axis=1) if spec.bs > 1 else e_tile
    return _decode_block(c_tile[..., None], e_lanes, spec)[..., 0]


def decode_lanes(c, e, spec: F.FrszSpec):
    """(R, bn) codes + (R, bn/bs) exps -> (R, bn) values, 128 lanes at a
    time: every slice is lane-aligned and each chunk's exponents come from
    single-lane broadcasts (:func:`lane_exponents`)."""
    chunks = []
    for k in range(0, c.shape[1], LANES):
        w = min(LANES, c.shape[1] - k)
        e_k = lane_exponents(e, spec.bs, k // spec.bs, w)
        chunks.append(_decode_values(c[:, k:k + w], e_k, spec))
    return chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=1)


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


def _valid(start, shape, dim: int, size: int):
    """Mask of the tile positions along ``dim`` that lie inside ``size``."""
    return start + jax.lax.broadcasted_iota(jnp.int32, shape, dim) < size


# ---------------------------------------------------------------------------
# Y (M, q) = decompress(V) @ W (q, N)^T
# ---------------------------------------------------------------------------


def _dots_kernel(c_ref, e_ref, w_ref, o_ref, comp_ref, *, spec: F.FrszSpec,
                 n: int, bn: int):
    k = pl.program_id(1)
    vals = decode_lanes(c_ref[...], e_ref[...], spec)
    w = w_ref[...].astype(vals.dtype)
    if n % bn:                    # ragged last n tile: zero both operands
        vals = jnp.where(_valid(k * bn, (1, bn), 1, n), vals, 0)
        w = jnp.where(_valid(k * bn, (1, bn), 1, n), w, 0)
    part = jax.lax.dot_general(vals, w, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=spec.dtype)
    _kahan_accumulate(o_ref, comp_ref, part, k)


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


def _combine_kernel(c_ref, e_ref, y_ref, o_ref, comp_ref, *,
                    spec: F.FrszSpec, m: int, bm: int):
    k = pl.program_id(1)
    vals = decode_lanes(c_ref[...], e_ref[...], spec)
    y = y_ref[...].astype(vals.dtype)
    if m % bm:                    # ragged last row tile: zero both operands
        vals = jnp.where(_valid(k * bm, (bm, 1), 0, m), vals, 0)
        y = jnp.where(_valid(k * bm, (1, bm), 1, m), y, 0)
    part = jnp.dot(y, vals, precision=_HIGHEST,
                   preferred_element_type=spec.dtype)
    _kahan_accumulate(o_ref, comp_ref, part, k)


def combine_2d(codes, exps, Y, spec: F.FrszSpec, *, bm: int, bn: int,
               interpret: bool = False):
    """codes (M, N), exps (M, N/bs), Y (q, M) -> out (q, N).

    Grid iterates N-tiles in the *outer* loop and M-tiles inner, so each
    output tile is finalized once (the M reduction is innermost); a ragged
    last N tile only writes columns that exist.
    """
    m, n = codes.shape
    q = Y.shape[0]
    eb = exps.shape[1] if bn == n else bn // spec.bs
    return pl.pallas_call(
        functools.partial(_combine_kernel, spec=spec, m=m, bm=bm),
        grid=(pl.cdiv(n, bn), pl.cdiv(m, bm)),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda j, k: (k, j)),
            pl.BlockSpec((bm, eb), lambda j, k: (k, j)),
            pl.BlockSpec((q, bm), lambda j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((q, bn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((q, n), spec.dtype),
        scratch_shapes=[pltpu.VMEM((q, bn), spec.dtype)],
        interpret=interpret,
    )(codes, exps, Y)
