"""Pallas TPU kernels for FRSZ2 compress / decompress.

TPU adaptation of the paper's CUDA design (Sec. IV-C):

* the CUDA warp (32 threads, warp-shuffle ``e_max`` reduce) becomes the
  128-lane VREG row: with ``bs == 128`` the block's ``e_max`` is a lane-wise
  ``max`` of a single register row — the cheapest possible reduction;
* ``__clz`` becomes ``jax.lax.clz`` (a JAX primitive, vectorized on the VPU);
* codes and exponents live in *separate* arrays (paper optimization (5)):
  index arithmetic stays trivial and every memory stream is contiguous;
* only aligned code widths l in {8, 16, 32} have kernels (paper
  optimization (3): separate routines for l == 2^x; on TPU the unaligned
  widths are strictly worse because vector loads want lane alignment —
  the pure-jnp codec still supports them for fidelity studies).

Layout convention for all kernels: codes are presented as a 2-D array of
shape (M, 128) — ``M = nb * bs / 128`` rows of 128 lanes — and exponents as
(M, G) where ``G = 128 / bs`` exponents cover one row (``bs`` divides 128).
Wrappers in ``ops.py`` do the reshaping / padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import frsz2 as F
from repro.core.frsz2 import _decode_values, _encode_values, _split_ieee

LANES = 128


def lane_exponents(e: jax.Array, bs: int, first: int, width: int = LANES
                   ) -> jax.Array:
    """Per-lane exponents of one ``width``-lane code chunk.

    ``e (R, *)`` holds block exponents; the chunk's first block is column
    ``first`` and ``bs`` divides 128.  Built from single-lane broadcasts and
    selects only: Mosaic lowers neither the ``(R, G) -> (R, G, bs)``
    reshape nor ``jnp.repeat`` along lanes.
    """
    R = e.shape[0]
    out = jnp.broadcast_to(e[:, first:first + 1], (R, width))
    if bs < width:
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, width), 1)
        for g in range(1, -(-width // bs)):
            out = jnp.where(lane >= g * bs, e[:, first + g:first + g + 1], out)
    return out


def block_max(e: jax.Array, bs: int) -> list[jax.Array]:
    """``(R, 128)`` signed per-lane exponents -> ``128 / bs`` ``(R, 1)``
    block maxima (Mosaic reduces signed, not unsigned, integers)."""
    if bs >= LANES:
        return [e.max(axis=1, keepdims=True)]
    lane = jax.lax.broadcasted_iota(jnp.int32, e.shape, 1)
    return [jnp.where((lane >= g * bs) & (lane < (g + 1) * bs), e, 0)
            .max(axis=1, keepdims=True) for g in range(LANES // bs)]


# ---------------------------------------------------------------------------
# decompress
# ---------------------------------------------------------------------------


def _decompress_kernel(c_ref, e_ref, o_ref, *, spec: F.FrszSpec):
    e = lane_exponents(e_ref[...], spec.bs, 0)
    o_ref[...] = _decode_values(c_ref[...], e, spec)


def decompress_2d(codes2d: jax.Array, exps2d: jax.Array, spec: F.FrszSpec,
                  *, block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """codes2d: (M, 128) aligned codes; exps2d: (M, G).  Returns (M, 128) f32."""
    M = codes2d.shape[0]
    G = exps2d.shape[1]
    assert M % block_rows == 0, (M, block_rows)
    grid = (M // block_rows,)
    return pl.pallas_call(
        functools.partial(_decompress_kernel, spec=spec),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, G), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, LANES), spec.dtype),
        interpret=interpret,
    )(codes2d, exps2d)


# ---------------------------------------------------------------------------
# compress
# ---------------------------------------------------------------------------


def _compress_kernel(x_ref, c_ref, e_ref, *, spec: F.FrszSpec):
    # bs <= 128 only: the block max never crosses a VREG row (ops.py enforces)
    sign, e, sig = _split_ieee(x_ref[...], spec)
    e = e.astype(jnp.int32)
    emax = block_max(e, spec.bs)
    for g, eg in enumerate(emax):
        e_ref[:, g:g + 1] = eg.astype(e_ref.dtype)
    emax_lanes = emax[0]
    if len(emax) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, e.shape, 1)
        for g in range(1, len(emax)):
            emax_lanes = jnp.where(lane >= g * spec.bs, emax[g], emax_lanes)
    c = _encode_values(sign, e, sig, emax_lanes, spec)
    c_ref[...] = c.astype(c_ref.dtype)


def compress_2d(x2d: jax.Array, spec: F.FrszSpec, *, block_rows: int = 256,
                interpret: bool = False):
    """x2d: (M, 128) values.  Returns codes (M, 128), exps (M, G)."""
    M = x2d.shape[0]
    assert M % block_rows == 0, (M, block_rows)
    G = max(1, LANES // spec.bs)
    grid = (M // block_rows,)
    code_dt = F._code_dtype(spec.l)
    return pl.pallas_call(
        functools.partial(_compress_kernel, spec=spec),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, G), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, LANES), code_dt),
            jax.ShapeDtypeStruct((M, G), spec.exp_dtype),
        ],
        interpret=interpret,
    )(x2d)
