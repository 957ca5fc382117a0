"""Pure-jnp oracles for every Pallas kernel in this package.

Each function here defines the *semantics*; the kernels in
``frsz2_kernel.py`` / ``frsz2_dot.py`` / ``decode_attn.py`` must match these
to within float tolerance (exactly, for the integer codec paths).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import frsz2 as F


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------


def compress_ref(x: jax.Array, spec: F.FrszSpec):
    """Returns (codes, exps) with codes shaped ``batch + (nb, bs)``."""
    bc = F.compress(x, spec)
    return bc.codes, bc.exps


def decompress_ref(codes: jax.Array, exps: jax.Array, spec: F.FrszSpec,
                   n: int | None = None):
    nb, bs = codes.shape[-2], codes.shape[-1]
    if n is None:
        n = nb * bs
    bc = F.BlockCompressed(codes=codes, exps=exps, n=n, spec=spec)
    return F.decompress(bc)


# ---------------------------------------------------------------------------
# fused decompress + matvec (the Accessor read path of CB-GMRES)
# ---------------------------------------------------------------------------


def matvec_ref(codes, exps, x, spec: F.FrszSpec):
    """y[i] = sum_j decompress(V)[i, j] * x[j], contracted in float64.

    codes: (m, nb, bs); exps: (m, nb); x: (nb*bs,)  ->  y: (m,)
    """
    V = decompress_ref(codes, exps, spec).astype(jnp.float64)  # (m, n_pad)
    return V @ x.astype(V.dtype)


def rmatvec_ref(codes, exps, h, spec: F.FrszSpec):
    """y[j] = sum_i h[i] * decompress(V)[i, j], contracted in float64.

    codes: (m, nb, bs); exps: (m, nb); h: (m,)  ->  y: (nb*bs,)
    """
    V = decompress_ref(codes, exps, spec).astype(jnp.float64)
    return h.astype(V.dtype) @ V


# ---------------------------------------------------------------------------
# flash-decode attention over an FRSZ2-compressed KV cache
# ---------------------------------------------------------------------------


def decode_attn_ref(q, kcodes, kexps, vcodes, vexps, lengths, spec: F.FrszSpec,
                    sm_scale: float | None = None):
    """Single-token decode attention, GQA, compressed KV.

    q:       (B, H, D)        new-token queries
    kcodes:  (B, Hkv, S, D_cb) codes for K, compressed along D (bs == D)
    kexps:   (B, Hkv, S, nb)
    lengths: (B,) int32       valid cache length per sequence
    returns: (B, H, D)
    """
    B, H, D = q.shape
    Hkv = kcodes.shape[1]
    S = kcodes.shape[2]
    G = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    k = decompress_ref(kcodes.reshape(B, Hkv, S, -1, spec.bs),
                       kexps, spec)[..., :D]          # (B, Hkv, S, D)
    v = decompress_ref(vcodes.reshape(B, Hkv, S, -1, spec.bs),
                       vexps, spec)[..., :D]
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    logits = jnp.einsum("bhgd,bhsd->bhgs", qg, k.astype(jnp.float32)) * sm_scale
    mask = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgs,bhsd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)
