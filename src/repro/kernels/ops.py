"""Public, jit-friendly wrappers around the Pallas FRSZ2 kernels.

Handles layout and padding so callers can use logical shapes.

Dispatch rule, keyed on the backend: kernels run compiled on TPU and in
Pallas interpret mode everywhere else.  Tests may pin interpret mode with
``repro.kernels.ops.INTERPRET = True`` or an explicit ``interpret=``
argument; on a TPU backend such a pin is an error, so no solver-path kernel
can silently run interpreted on the chip.

Kernel-path constraints (TPU alignment, see frsz2_kernel.py docstring):
  * aligned code widths only: l in {8, 16, 32}
  * bs divides 128 (a block never straddles a VREG row)

Formats outside those constraints use the pure-jnp codec; every shape
inside them runs the kernel (ragged edges are padded or masked, never
routed to a jnp twin).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import frsz2 as F
from repro.kernels import frsz2_kernel as K
from repro.kernels import frsz2_dot as KD
from repro.kernels import decode_attn as KA

LANES = 128

#: interpret pin for tests: ``None`` follows the backend; ``True`` forces
#: interpret mode (refused on TPU), ``False`` forces compiled kernels.
INTERPRET: bool | None = None


def _resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode for one kernel call: the explicit argument, else the
    :data:`INTERPRET` pin, else ``True`` exactly when the backend is not TPU.
    """
    if interpret is None:
        interpret = INTERPRET
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise RuntimeError(
            "Pallas interpret mode was requested on a TPU backend; the chip "
            "path runs compiled kernels only (unset ops.INTERPRET and drop "
            "interpret=True)")
    return bool(interpret)


def kernel_supported(spec: F.FrszSpec) -> bool:
    return spec.aligned and spec.l <= 32 and LANES % spec.bs == 0


@functools.lru_cache(maxsize=4096)
def _pick_block_rows(M: int, cap: int = 256) -> tuple[int, int]:
    """``(M_pad, br)``: rows padded to a supported multiple, then tiled.

    Rows are padded up to the f32 sublane multiple (8) first, so the chosen
    tile is always >= 8 rows (never a row-per-grid-step kernel for prime
    or odd ``M``); callers slice the pad rows back off the kernel output.
    """
    M_pad = max(8, -(-M // 8) * 8)
    for br in (cap, 128, 64, 32, 16, 8):
        if br <= cap and M_pad % br == 0:
            return M_pad, br
    return M_pad, 8


# ---------------------------------------------------------------------------
# compress / decompress with logical (batch..., n) shapes
# ---------------------------------------------------------------------------


def _lane_rows(flat: jax.Array, width: int = LANES):
    """1-D ``flat`` -> zero-padded ``(M_pad, width)`` rows and the tile
    rows ``br`` of the kernel grid."""
    total = flat.shape[0]
    M_pad, br = _pick_block_rows(-(-total // width))
    return jnp.pad(flat, (0, M_pad * width - total)).reshape(-1, width), br


def compress(x: jax.Array, spec: F.FrszSpec, *, interpret: bool | None = None
             ) -> F.BlockCompressed:
    """Kernel-backed version of ``repro.core.frsz2.compress``.

    Blocks are laid out ``128 / bs`` to a row of 128 lanes; zero blocks pad
    the last row and are sliced off (``bs`` divides 128, so no block
    straddles a row).
    """
    if not kernel_supported(spec):
        return F.compress(x, spec)
    *batch, n = x.shape
    nb = -(-n // spec.bs)
    xp = jnp.pad(x, [(0, 0)] * len(batch) + [(0, nb * spec.bs - n)])
    flat = xp.astype(spec.dtype).reshape(-1)
    x2d, br = _lane_rows(flat)
    codes2d, exps2d = K.compress_2d(x2d, spec, block_rows=br,
                                    interpret=_resolve_interpret(interpret))
    total = flat.shape[0]
    codes = codes2d.reshape(-1)[:total].reshape(*batch, nb, spec.bs)
    exps = exps2d.reshape(-1)[:total // spec.bs].reshape(*batch, nb)
    return F.BlockCompressed(codes=codes, exps=exps, n=n, spec=spec)


def decompress(bc: F.BlockCompressed, *, interpret: bool | None = None) -> jax.Array:
    """Kernel-backed version of ``repro.core.frsz2.decompress``."""
    spec = bc.spec
    if not kernel_supported(spec):
        return F.decompress(bc)
    *batch, nb, bs = bc.codes.shape
    codes2d, br = _lane_rows(bc.codes.reshape(-1))
    exps2d, _ = _lane_rows(bc.exps.reshape(-1), LANES // bs)
    x2d = K.decompress_2d(codes2d, exps2d, spec, block_rows=br,
                          interpret=_resolve_interpret(interpret))
    x = x2d.reshape(-1)[:nb * bs * int(np.prod(batch, dtype=np.int64))]
    return x.reshape(*batch, nb * bs)[..., : bc.n]


# ---------------------------------------------------------------------------
# fused decompress-contractions over a compressed row basis V (m, n)
# ---------------------------------------------------------------------------


# A whole reduction axis up to this size runs as ONE kernel tile (a single
# MXU contraction, no cross-tile accumulation).  Longer axes tile at the
# largest multiple of ``128 * bs`` (lane-aligned exponent tiles) up to 4096
# values, or at ``128 * bs`` itself when that is larger.
MAX_SINGLE_TILE = 8192
#: f32 bytes of one decoded tile; sizes the row tile (VMEM budget).
TILE_BYTES = 4 * 1024 * 1024


def _tile_n(n: int, bs: int) -> int:
    unit = LANES * bs
    if n <= max(MAX_SINGLE_TILE, unit):
        return n
    return unit * max(1, 4096 // unit)


def _tile_m(m: int, bn: int, align: int) -> int:
    """Rows per tile: all ``m`` when the decoded tile fits the budget, else
    the largest multiple of ``align`` that does."""
    cap = max(align, TILE_BYTES // (4 * bn) // align * align)
    return m if m <= cap else cap


@functools.lru_cache(maxsize=4096)
def _dot_layout(m: int, n: int, bs: int) -> tuple[int, int]:
    """``(bm, bn)`` of the dots kernel over an ``(m, n)`` code matrix.

    Row tiles are multiples of 32 (the sublane tile of 8-bit codes).
    Memoized on the shape key: repeated same-shape solves — every warm
    GMRES cycle — skip the host-side tile arithmetic entirely.
    """
    bn = _tile_n(n, bs)
    return _tile_m(m, bn, 32), bn


@functools.lru_cache(maxsize=4096)
def _reduce_layout(m: int, n: int, bs: int) -> tuple[int, int]:
    """``(bm, bn)`` of the combine kernel: its row tile is also the lane
    dim of the coefficient block, so a partial row tile is 128-aligned."""
    bn = _tile_n(n, bs)
    return _tile_m(m, bn, LANES), bn


def _basis_2d(bc: F.BlockCompressed):
    """(m, nb, bs) codes -> (m, nb * bs) element codes + (m, nb) exps."""
    m = bc.codes.shape[0]
    return bc.codes.reshape(m, -1), bc.exps


def _dots(codes, exps, W, spec, interpret):
    bm, bn = _dot_layout(*codes.shape, spec.bs)
    return KD.dots_2d(codes, exps, W.astype(spec.dtype), spec, bm=bm, bn=bn,
                      interpret=_resolve_interpret(interpret))


def _combine(codes, exps, Y, spec, interpret):
    bm, bn = _reduce_layout(*codes.shape, spec.bs)
    return KD.combine_2d(codes, exps, Y.astype(spec.dtype), spec, bm=bm,
                         bn=bn, interpret=_resolve_interpret(interpret))


def _pad_to(x: jax.Array, n: int) -> jax.Array:
    return x if x.shape[-1] == n else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, n - x.shape[-1])])


def matvec(bc: F.BlockCompressed, x: jax.Array, *,
           interpret: bool | None = None) -> jax.Array:
    """y = decompress(V) @ x  for V (m, n) compressed row-wise.

    Accepts leading batch dims on the basis (codes ``(..., m, nb, bs)`` with
    ``x (..., n)``): batched calls vmap onto the 2-D kernel.
    """
    spec = bc.spec
    if bc.codes.ndim > 3:
        return jax.vmap(
            lambda c, e, xx: matvec(
                F.BlockCompressed(codes=c, exps=e, n=bc.n, spec=spec), xx,
                interpret=interpret)
        )(bc.codes, bc.exps, x)
    if not kernel_supported(spec):
        V = F.decompress(bc)
        return V @ x.astype(V.dtype)
    codes, exps = _basis_2d(bc)
    return _dots(codes, exps, _pad_to(x[None, :], codes.shape[1]), spec,
                 interpret)[:, 0]


def rmatvec(bc: F.BlockCompressed, h: jax.Array, *,
            interpret: bool | None = None) -> jax.Array:
    """y = h @ decompress(V)  for V (m, n) compressed row-wise.

    Accepts leading batch dims on the basis (see :func:`matvec`).
    """
    spec = bc.spec
    if bc.codes.ndim > 3:
        return jax.vmap(
            lambda c, e, hh: rmatvec(
                F.BlockCompressed(codes=c, exps=e, n=bc.n, spec=spec), hh,
                interpret=interpret)
        )(bc.codes, bc.exps, h)
    if not kernel_supported(spec):
        V = F.decompress(bc)
        return h.astype(V.dtype) @ V
    codes, exps = _basis_2d(bc)
    return _combine(codes, exps, h[None, :], spec, interpret)[0, : bc.n]


# ---------------------------------------------------------------------------
# fused block contractions over a flattened block basis V (m, p * n_seg)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=4096)
def _block_layout(p: int, n_flat: int, bs: int) -> int:
    """Segment length ``n_seg`` of a flattened block store of ``p``
    segments, viewed by the kernels as ``(m * p, n_seg)`` rows.

    The view requires the segment to be a whole number of codec blocks;
    ``BlockBasisAccessor`` aligns segments via ``block_align`` so every
    store it builds satisfies this.
    """
    if p <= 0 or n_flat % p or (n_flat // p) % bs:
        raise ValueError(
            f"flattened block row of {n_flat} values does not split into "
            f"p={p} segments of whole {bs}-value codec blocks")
    return n_flat // p


def _block_basis_2d(bc: F.BlockCompressed, p: int):
    """Flat (m, nb, bs) codes -> (m*p, n_seg) element codes + exps."""
    m, nb, bs = bc.codes.shape
    n_seg = _block_layout(p, nb * bs, bc.spec.bs)
    return (bc.codes.reshape(m * p, n_seg),
            bc.exps.reshape(m * p, n_seg // bc.spec.bs), n_seg)


def block_dots(bc: F.BlockCompressed, W: jax.Array, *, p: int,
               interpret: bool | None = None):
    """``H (m, p, q) = einsum('ian,bn->iab', decompress(V), W)`` fused.

    ``bc`` holds ``m`` flattened block rows of ``p`` segment-aligned
    per-RHS segments; ``W (q, n_log)`` with ``n_log <= n_seg`` is
    zero-padded to the segment length (pad columns of the store decode to
    exact zeros, so the contraction is unaffected).  Returns ``None`` for
    a spec without kernels — the caller owns the jnp route.
    """
    if not kernel_supported(bc.spec):
        return None
    codes, exps, n_seg = _block_basis_2d(bc, p)
    Y = _dots(codes, exps, _pad_to(W, n_seg), bc.spec, interpret)
    return Y.reshape(bc.codes.shape[0], p, W.shape[0])


def block_combine(bc: F.BlockCompressed, Y: jax.Array, *, p: int,
                  interpret: bool | None = None):
    """``out (q, n_seg) = einsum('iab,ian->bn', Y, decompress(V))`` fused.

    ``Y (m, p, q)`` are the block couplings; the caller trims the result's
    segment padding back to the logical vector length.  Returns ``None``
    for a spec without kernels.
    """
    if not kernel_supported(bc.spec):
        return None
    codes, exps, _ = _block_basis_2d(bc, p)
    return _combine(codes, exps, Y.reshape(codes.shape[0], -1).T, bc.spec,
                    interpret)


# ---------------------------------------------------------------------------
# decode attention over compressed KV
# ---------------------------------------------------------------------------


def decode_attention(q: jax.Array, k_bc: F.BlockCompressed,
                     v_bc: F.BlockCompressed, lengths: jax.Array, *,
                     sm_scale: float | None = None, bs_s: int | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """q (B, H, D); k/v compressed caches with logical shape (B, Hkv, S, D).

    Returns (B, H, D).  Requires D == spec.bs * nbd with aligned spec.
    """
    spec = k_bc.spec
    B, H, D = q.shape
    _, Hkv, S, nbd = k_bc.exps.shape
    G = H // Hkv
    interpret = _resolve_interpret(interpret)
    if not kernel_supported(spec):
        from repro.kernels import ref
        return ref.decode_attn_ref(
            q, k_bc.codes.reshape(B, Hkv, S, -1), k_bc.exps,
            v_bc.codes.reshape(B, Hkv, S, -1), v_bc.exps,
            lengths.reshape(-1), spec, sm_scale=sm_scale)
    kcodes = k_bc.codes.reshape(B, Hkv, S, D)
    vcodes = v_bc.codes.reshape(B, Hkv, S, D)
    if bs_s is None:
        bs_s = 512
        while S % bs_s:
            bs_s //= 2
    qg = q.reshape(B, Hkv, G, D)
    # pad G to the f32 sublane count (8) for TPU tiling
    Gp = max(8, G)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    out = KA.decode_attn(qg, kcodes, k_bc.exps, vcodes, v_bc.exps,
                         lengths.reshape(B, 1).astype(jnp.int32), spec,
                         sm_scale=sm_scale, bs_s=bs_s, interpret=interpret)
    return out[:, :, :G, :].reshape(B, H, D)
