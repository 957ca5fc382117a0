"""Accessor: storage format ⊥ arithmetic format (Ginkgo's interface, in JAX).

The paper integrates FRSZ2 into CB-GMRES through Ginkgo's *Accessor*: all
arithmetic happens in a high-precision "arithmetic format" while the Krylov
basis is persisted in a "storage format" (f64/f32/f16 cast, or FRSZ2 codes).
Reads decompress on the fly; writes compress whole blocks.

This module reproduces that contract for JAX.  A :class:`BasisAccessor`
manages a *row basis* ``V`` of fixed capacity ``(m, n)`` — the Krylov buffer —
and exposes exactly the operations CB-GMRES needs (paper Fig. 1):

  * ``write_row(store, j, v)``   — append/overwrite basis vector j (compress)
  * ``read_row(store, j)``       — random access decompress of one row
  * ``dots(store, w)``           — ``V @ w``      (orthogonalization, step 4)
  * ``combine(store, h)``        — ``h @ V``      (update / solution, steps 4+17)

Storage-format protocol
-----------------------

Every storage format is a small frozen dataclass implementing
:class:`StorageFormat`.  The accessor performs **no** dispatch on concrete
format classes: each format owns its full read/write/dot path, including any
kernel routing (``FrszFormat`` sends ``dots``/``combine`` through the fused
decompress-dot Pallas kernels in ``repro.kernels.frsz2_dot`` so codes are
expanded in-register).  All arithmetic is performed in ``arith_dtype``
regardless of storage.  Formats are frozen dataclasses so they can be static
args to jit and live inside pytree aux data.

Adding a new storage format takes two steps:

1. subclass :class:`StorageFormat` and implement ``empty`` / ``write_row`` /
   ``read_row`` / ``read_all`` / ``nbytes`` (``dots``/``combine`` have
   generic read_all-based defaults you can override with a fused path);
2. register a builder in the :data:`FORMATS` table with
   :func:`register_format` — either under an exact name (``"float64"``) or
   under a family prefix (``"frsz2"`` matches ``frsz2_32``, ``frsz2_16``, …).

``format_by_name`` resolves names through that one table; nothing else in
the solver stack needs to change.
"""
from __future__ import annotations

import dataclasses
import math
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp

from repro import runtime
from repro.core import frsz2 as F

#: VREG lane count of the Pallas kernel layouts (repro.kernels.ops.LANES,
#: duplicated here so the core protocol does not import the kernel stack).
_KERNEL_LANES = 128

#: Precision of every dense product in the solve (basis contractions here,
#: the small Hessenberg/QR products in ``repro.solver``).  A TPU's default
#: f32 pass rounds operands to bfloat16, which would cap Arnoldi
#: orthogonality near 2^-8; HIGHEST is full f32 there and changes nothing
#: in f64.  The Pallas kernels contract at the same precision.
HIGHEST = jax.lax.Precision.HIGHEST

__all__ = [
    "StorageFormat",
    "NativeFormat",
    "FrszFormat",
    "MixedFormat",
    "ShardedFormat",
    "BasisAccessor",
    "BlockBasisAccessor",
    "auto_mixed_head",
    "register_format",
    "format_by_name",
    "FORMATS",
]


# ---------------------------------------------------------------------------
# Storage-format protocol
# ---------------------------------------------------------------------------


class StorageFormat:
    """Protocol + generic defaults for Krylov-basis storage formats.

    A format stores an ``(m, n)`` row basis in an arbitrary representation
    (its *store*, any pytree of arrays) and answers the four Accessor
    operations.  ``read_row``/``read_all`` take the arithmetic dtype and the
    logical row length ``n`` (stores may be block-padded beyond ``n``).

    ``dots``/``combine`` are the two memory-bound hot loops.  The defaults
    below materialize the basis via ``read_all``; formats with a fused
    decompress-dot path (e.g. :class:`FrszFormat` with ``use_kernels``)
    override them.  Row masking is applied by :class:`BasisAccessor`, not by
    formats.
    """

    # -- identity / accounting ------------------------------------------------
    @property
    def name(self) -> str:  # pragma: no cover - overridden
        raise NotImplementedError

    def bits_per_value(self) -> float:  # pragma: no cover - overridden
        raise NotImplementedError

    def eps(self) -> float:
        """Relative storage error bound of one round-trip through the format.

        The contract behind adaptive-policy auto-thresholds
        (:meth:`repro.solver.pipeline.AdaptivePolicy.from_target`): a basis
        vector written and read back differs from the original by at most
        ``eps()`` in the format's reference scale (machine epsilon for
        native dtypes, the per-block max for FRSZ2).
        """
        raise NotImplementedError(
            f"{type(self).__name__} does not report a storage epsilon; "
            "implement eps() to use it with auto-threshold policies")

    def nbytes(self, m: int, n: int) -> int:  # pragma: no cover
        raise NotImplementedError

    # -- store management -----------------------------------------------------
    def empty(self, m: int, n: int):  # pragma: no cover - overridden
        raise NotImplementedError

    def rows(self, store) -> int:
        """Row capacity of ``store`` (static)."""
        return jax.tree.leaves(store)[0].shape[0]

    # -- element access -------------------------------------------------------
    def write_row(self, store, j, v):  # pragma: no cover - overridden
        raise NotImplementedError

    def read_row(self, store, j, arith_dtype, n: int):  # pragma: no cover
        raise NotImplementedError

    def read_all(self, store, arith_dtype, n: int):  # pragma: no cover
        raise NotImplementedError

    # -- hot loops (generic defaults) ----------------------------------------
    def dots(self, store, w, arith_dtype, n: int):
        """h = V @ w (unmasked)."""
        V = self.read_all(store, arith_dtype, n)
        return jnp.matmul(V, w.astype(arith_dtype), precision=HIGHEST)

    def reduce_partials(self, x):
        """Reduce a locally-computed contraction against the basis.

        Identity for local formats.  :class:`ShardedFormat` overrides this
        with a psum over its mesh axis (on the transport its ``dots``
        already uses), so accessor-level contractions that cannot route
        through ``dots`` — the block-basis ``V^T W`` products — still
        defer the wire decision to the format.
        """
        return x

    def combine(self, store, h, arith_dtype, n: int):
        """y = h @ V (unmasked)."""
        V = self.read_all(store, arith_dtype, n)
        return jnp.matmul(h.astype(arith_dtype), V, precision=HIGHEST)

    # -- block-basis contract -------------------------------------------------
    def block_align(self) -> int:
        """Per-RHS segment alignment for flattened block rows.

        :class:`BlockBasisAccessor` flattens each ``(p, n)`` block row to
        one storage row of ``p`` segments, each padded to this multiple.
        Formats whose representation has internal block structure return
        an alignment that keeps every segment starting on a block *and*
        kernel-lane boundary (so the fused block kernels can view the flat
        row as ``(p, n_seg)`` with no codec block straddling a segment
        edge); ``1`` means pack segments tightly.
        """
        return 1

    def block_dots(self, store, W, arith_dtype, n: int, p: int, n_seg: int):
        """``H[i,a,b] = <V[i,a], W[b]>`` over the flattened block basis
        (unmasked, local — :class:`ShardedFormat` adds the reduction).

        The store holds rows of ``p`` segments of ``n_seg`` elements; the
        trailing ``n_seg - n`` of each segment are zero padding.
        """
        V = self.read_all(store, arith_dtype, p * n_seg)
        V = V.reshape(-1, p, n_seg)[..., :n]
        return jnp.einsum("ian,bn->iab", V, W.astype(arith_dtype),
                          precision=HIGHEST)

    def block_combine(self, store, Y, arith_dtype, n: int, p: int,
                      n_seg: int):
        """``out[b] = sum_{i,a} Y[i,a,b] V[i,a]``, returned in the padded
        segment layout ``(b, n_seg)`` (the accessor trims to ``n``)."""
        V = self.read_all(store, arith_dtype, p * n_seg).reshape(-1, p, n_seg)
        return jnp.einsum("iab,ian->bn", Y.astype(arith_dtype), V,
                          precision=HIGHEST)


# ---------------------------------------------------------------------------
# Concrete formats
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NativeFormat(StorageFormat):
    """Plain cast-to-dtype storage (CB-GMRES float64/float32/float16 modes)."""

    dtype: Any = jnp.float32

    @property
    def name(self) -> str:
        return jnp.dtype(self.dtype).name

    def bits_per_value(self) -> float:
        return jnp.dtype(self.dtype).itemsize * 8

    def eps(self) -> float:
        return float(jnp.finfo(self.dtype).eps)

    def empty(self, m: int, n: int):
        return jnp.zeros((m, n), self.dtype)

    def write_row(self, store, j, v):
        return store.at[j].set(v.astype(self.dtype))

    def read_row(self, store, j, arith_dtype, n: int):
        return store[j].astype(arith_dtype)

    def read_all(self, store, arith_dtype, n: int):
        return store.astype(arith_dtype)

    def nbytes(self, m: int, n: int) -> int:
        return m * n * jnp.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class FrszFormat(StorageFormat):
    """FRSZ2 block-compressed storage (the paper's contribution).

    ``use_kernels`` routes ``dots``/``combine`` through the fused Pallas
    decompress-dot kernels (interpret-mode on CPU); otherwise the pure-jnp
    codec is used.  Semantics are identical (tests assert this).

    The store keeps each row's codes flat, ``(m, nb * width)``, the code
    matrix the kernels read: a ``(m, nb, bs)`` store would be laid out
    otherwise on a TPU, and every pass over it would copy the whole store
    into the kernels' layout first.
    """

    spec: F.FrszSpec = F.FRSZ2_32
    use_kernels: bool = False

    @property
    def name(self) -> str:
        return f"frsz2_{self.spec.l}"

    def bits_per_value(self) -> float:
        return F.bits_per_value(self.spec)

    def eps(self) -> float:
        # l-bit code = sign + (l-1) bits of the value normalized to the
        # block max exponent: truncation error <= 2^-(l-2) of the block max
        # (the documented frsz2_16 ~2^-14 / frsz2_32 ~2^-30 bounds)
        return 2.0 ** (2 - self.spec.l)

    def _nb(self, n: int) -> int:
        return -(-n // self.spec.bs)

    def _width(self) -> int:
        """Code words per block: ``bs`` codes, or the packed uint32s."""
        spec = self.spec
        return spec.bs if spec.aligned else spec.words_per_block

    def empty(self, m: int, n: int):
        spec = self.spec
        nb = self._nb(n)
        dtype = F._code_dtype(spec.l) if spec.aligned else jnp.uint32
        codes = jnp.zeros((m, nb * self._width()), dtype)
        exps = jnp.zeros((m, nb), spec.exp_dtype)
        return {"codes": codes, "exps": exps}

    def rows(self, store) -> int:
        return store["codes"].shape[0]

    def write_row(self, store, j, v):
        with jax.named_scope("compress"):
            bc = F.compress(v.astype(self.spec.dtype), self.spec)
        return {
            "codes": store["codes"].at[j].set(bc.codes.reshape(-1)),
            "exps": store["exps"].at[j].set(bc.exps),
        }

    def _as_bc(self, store, n: int) -> F.BlockCompressed:
        exps = store["exps"]
        codes = store["codes"].reshape(*exps.shape, self._width())
        return F.BlockCompressed(codes=codes, exps=exps, n=n, spec=self.spec)

    def read_row(self, store, j, arith_dtype, n: int):
        row = {"codes": store["codes"][j][None], "exps": store["exps"][j][None]}
        return F.decompress(self._as_bc(row, n))[0].astype(arith_dtype)

    def read_all(self, store, arith_dtype, n: int):
        return F.decompress(self._as_bc(store, n)).astype(arith_dtype)

    def dots(self, store, w, arith_dtype, n: int):
        if self.use_kernels:
            from repro.kernels import ops as kops

            bc = self._as_bc(store, n)
            return kops.matvec(bc, w.astype(self.spec.dtype)).astype(arith_dtype)
        return super().dots(store, w, arith_dtype, n)

    def combine(self, store, h, arith_dtype, n: int):
        if self.use_kernels:
            from repro.kernels import ops as kops

            bc = self._as_bc(store, n)
            return kops.rmatvec(bc, h.astype(self.spec.dtype)).astype(arith_dtype)
        return super().combine(store, h, arith_dtype, n)

    def block_align(self) -> int:
        # segments start on both a codec-block and a VREG-lane boundary:
        # the fused block kernels then view the flat row as (p, n_seg)
        # with no FRSZ2 block straddling a segment edge.  Quantization
        # boundaries inside the data region are bs-aligned either way, so
        # the jnp and kernel routes see identical stored values.
        return math.lcm(self.spec.bs, _KERNEL_LANES)

    def block_dots(self, store, W, arith_dtype, n: int, p: int, n_seg: int):
        if self.use_kernels:
            from repro.kernels import ops as kops

            H = kops.block_dots(self._as_bc(store, p * n_seg),
                                W.astype(self.spec.dtype), p=p)
            if H is not None:
                return H.astype(arith_dtype)
        return super().block_dots(store, W, arith_dtype, n, p, n_seg)

    def block_combine(self, store, Y, arith_dtype, n: int, p: int,
                      n_seg: int):
        if self.use_kernels:
            from repro.kernels import ops as kops

            out = kops.block_combine(self._as_bc(store, p * n_seg),
                                     Y.astype(self.spec.dtype), p=p)
            if out is not None:
                return out.astype(arith_dtype)
        return super().block_combine(store, Y, arith_dtype, n, p, n_seg)

    def nbytes(self, m: int, n: int) -> int:
        return m * F.storage_nbytes(n, self.spec)


@dataclasses.dataclass(frozen=True)
class MixedFormat(StorageFormat):
    """Mixed-precision basis: first ``k`` rows in ``head``, rest in ``tail``.

    The classic CB-GMRES accuracy hedge: early Krylov vectors carry most of
    the solution's signal, so keeping the first few in full precision while
    compressing the (many) later ones recovers nearly-f64 convergence at
    nearly-compressed bandwidth.  Enabled purely by the format protocol —
    the accessor and solver are unchanged.

    The store is ``{"head": head_store(k rows), "tail": tail_store(m-k)}``;
    row ``j`` routes to head iff ``j < k`` (jit-safe via ``lax.cond`` — ``j``
    may be a traced index inside the Arnoldi ``fori_loop``).
    """

    k: int = 2
    head: StorageFormat = NativeFormat(jnp.float64)
    tail: StorageFormat = FrszFormat(F.FRSZ2_32)

    @property
    def name(self) -> str:
        return f"mixed:{self.k}:{self.tail.name}"

    def bits_per_value(self) -> float:
        # amortized over a large basis the tail dominates; nbytes() is exact
        return self.tail.bits_per_value()

    def eps(self) -> float:
        return max(self.head.eps(), self.tail.eps())

    def _split(self, m: int) -> tuple[int, int]:
        kh = min(self.k, m)
        return kh, m - kh

    def empty(self, m: int, n: int):
        kh, kt = self._split(m)
        return {"head": self.head.empty(kh, n), "tail": self.tail.empty(kt, n)}

    def rows(self, store) -> int:
        return self.head.rows(store["head"]) + self.tail.rows(store["tail"])

    def write_row(self, store, j, v):
        kh = self.head.rows(store["head"])
        kt = self.tail.rows(store["tail"])

        def wh(s):
            jj = jnp.clip(j, 0, max(kh - 1, 0))
            return {"head": self.head.write_row(s["head"], jj, v),
                    "tail": s["tail"]}

        def wt(s):
            jj = jnp.clip(j - kh, 0, max(kt - 1, 0))
            return {"head": s["head"],
                    "tail": self.tail.write_row(s["tail"], jj, v)}

        if kt == 0:
            return wh(store)
        if kh == 0:
            return wt(store)
        return jax.lax.cond(j < kh, wh, wt, store)

    def read_row(self, store, j, arith_dtype, n: int):
        kh = self.head.rows(store["head"])
        kt = self.tail.rows(store["tail"])

        def rh(s):
            jj = jnp.clip(j, 0, max(kh - 1, 0))
            return self.head.read_row(s["head"], jj, arith_dtype, n)

        def rt(s):
            jj = jnp.clip(j - kh, 0, max(kt - 1, 0))
            return self.tail.read_row(s["tail"], jj, arith_dtype, n)

        if kt == 0:
            return rh(store)
        if kh == 0:
            return rt(store)
        return jax.lax.cond(j < kh, rh, rt, store)

    def read_all(self, store, arith_dtype, n: int):
        return jnp.concatenate(
            [self.head.read_all(store["head"], arith_dtype, n),
             self.tail.read_all(store["tail"], arith_dtype, n)], axis=0)

    def dots(self, store, w, arith_dtype, n: int):
        return jnp.concatenate(
            [self.head.dots(store["head"], w, arith_dtype, n),
             self.tail.dots(store["tail"], w, arith_dtype, n)], axis=0)

    def combine(self, store, h, arith_dtype, n: int):
        kh = self.head.rows(store["head"])
        return (self.head.combine(store["head"], h[:kh], arith_dtype, n)
                + self.tail.combine(store["tail"], h[kh:], arith_dtype, n))

    def block_align(self) -> int:
        # one shared alignment for both sub-stores: head and tail rows of
        # the same basis must agree on the segment layout
        return math.lcm(self.head.block_align(), self.tail.block_align())

    def block_dots(self, store, W, arith_dtype, n: int, p: int, n_seg: int):
        return jnp.concatenate(
            [self.head.block_dots(store["head"], W, arith_dtype, n, p, n_seg),
             self.tail.block_dots(store["tail"], W, arith_dtype, n, p,
                                  n_seg)], axis=0)

    def block_combine(self, store, Y, arith_dtype, n: int, p: int,
                      n_seg: int):
        kh = self.head.rows(store["head"])
        return (self.head.block_combine(store["head"], Y[:kh], arith_dtype,
                                        n, p, n_seg)
                + self.tail.block_combine(store["tail"], Y[kh:], arith_dtype,
                                          n, p, n_seg))

    def nbytes(self, m: int, n: int) -> int:
        kh, kt = self._split(m)
        return self.head.nbytes(kh, n) + self.tail.nbytes(kt, n)


@dataclasses.dataclass(frozen=True)
class ShardedFormat(StorageFormat):
    """Basis rows split across devices along the vector (n) dimension.

    Each device holds the local chunk of every Krylov vector in ``inner``
    storage; the accessor's ``n`` is the *local* chunk length.  The format
    must run inside ``jax.shard_map``/``pmap`` with ``axis_name`` bound
    (``repro.dist.sharding.basis_partition_specs`` gives the matching
    in/out specs):

      * ``dots`` — each device computes the partial dot products against
        its chunk, then reduces over ``axis_name``.  With
        ``compressed_transport`` (default) the partial sums travel as
        FRSZ2 codes through
        :func:`repro.dist.collectives.compressed_psum` — the paper's codec
        on the wire, exactly like the gradient all-reduce;
      * ``combine`` — purely local: the result is the local chunk of
        ``h @ V`` and stays sharded (no collective at all);
      * ``write_row``/``read_row`` — local compress/decompress of chunks.

    ``nbytes`` reports per-device (local) storage, matching the
    bandwidth-per-device roofline argument.
    """

    inner: StorageFormat = NativeFormat(jnp.float32)
    axis_name: str = "basis"
    compressed_transport: bool = True

    @property
    def name(self) -> str:
        return f"sharded:{self.inner.name}"

    def bits_per_value(self) -> float:
        return self.inner.bits_per_value()

    def eps(self) -> float:
        return self.inner.eps()

    def empty(self, m: int, n: int):
        return self.inner.empty(m, n)

    def rows(self, store) -> int:
        return self.inner.rows(store)

    def write_row(self, store, j, v):
        return self.inner.write_row(store, j, v)

    def read_row(self, store, j, arith_dtype, n: int):
        return self.inner.read_row(store, j, arith_dtype, n)

    def read_all(self, store, arith_dtype, n: int):
        return self.inner.read_all(store, arith_dtype, n)

    def dots(self, store, w, arith_dtype, n: int):
        local = self.inner.dots(store, w, arith_dtype, n)
        return self.reduce_partials(local).astype(arith_dtype)

    def reduce_partials(self, x):
        from repro.dist import collectives

        if self.compressed_transport:
            return collectives.compressed_psum(x, self.axis_name)
        return collectives.psum(x, self.axis_name)

    def combine(self, store, h, arith_dtype, n: int):
        return self.inner.combine(store, h, arith_dtype, n)

    def block_align(self) -> int:
        return self.inner.block_align()

    def block_dots(self, store, W, arith_dtype, n: int, p: int, n_seg: int):
        local = self.inner.block_dots(store, W, arith_dtype, n, p, n_seg)
        return self.reduce_partials(local).astype(arith_dtype)

    def block_combine(self, store, Y, arith_dtype, n: int, p: int,
                      n_seg: int):
        # purely local, like scalar combine: the result is the local chunk
        return self.inner.block_combine(store, Y, arith_dtype, n, p, n_seg)

    def nbytes(self, m: int, n: int) -> int:
        return self.inner.nbytes(m, n)


# ---------------------------------------------------------------------------
# Basis accessor: the Krylov-buffer contract
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BasisAccessor:
    """Fixed-capacity row basis V (m, n) in an arbitrary storage format.

    All four operations are jit-compatible (store is a pytree; j may be a
    traced index).  ``dots``/``combine`` accept a row mask so a growing
    Krylov basis can live in a fixed buffer under ``lax.fori_loop``.

    The accessor is format-agnostic: every operation delegates to the
    :class:`StorageFormat` protocol, and masking (the only accessor-level
    concern) is applied here — *after* the format's ``dots`` and *before*
    its ``combine`` so fused kernel paths see unmasked inputs.
    """

    fmt: Any
    m: int
    n: int
    arith_dtype: Any = jnp.float64

    def empty(self):
        return self.fmt.empty(self.m, self.n)

    def write_row(self, store, j, v):
        with jax.named_scope("store"):
            return self.fmt.write_row(store, j, v)

    def read_row(self, store, j):
        with jax.named_scope("decode"):
            return self.fmt.read_row(store, j, self.arith_dtype, self.n)

    def read_all(self, store):
        return self.fmt.read_all(store, self.arith_dtype, self.n)

    # -- hot loops ------------------------------------------------------------
    def dots(self, store, w, row_mask=None):
        """h = V @ w, masked rows zeroed.  (Orthogonalization dot products.)"""
        with jax.named_scope("dots"):
            h = self.fmt.dots(store, w, self.arith_dtype, self.n)
            if row_mask is not None:
                h = jnp.where(row_mask, h, 0.0)
            return h

    def combine(self, store, h, row_mask=None):
        """y = h @ V, masked rows excluded.  (Basis update / solution build.)"""
        with jax.named_scope("combine"):
            if row_mask is not None:
                h = jnp.where(row_mask, h, 0.0)
            return self.fmt.combine(store, h, self.arith_dtype, self.n)

    def nbytes(self) -> int:
        return self.fmt.nbytes(self.m, self.n)


@dataclasses.dataclass(frozen=True)
class BlockBasisAccessor:
    """Fixed-capacity basis of *block vectors* ``V (m, p, n)`` — the shared
    Krylov buffer of block-GMRES, stored through the unchanged
    :class:`StorageFormat` protocol.

    Each block row (the ``p`` simultaneous Krylov directions of one Arnoldi
    step) is flattened to a single storage row of ``p`` *segments*, one per
    right-hand side, each zero-padded to the format's ``block_align()``
    multiple (``n_seg``).  Native formats pack tightly (``n_seg == n``);
    FRSZ2 aligns segments to codec-block/VREG boundaries so the fused block
    kernels can view the flat row as ``(p, n_seg)`` with no block straddling
    a segment edge — zero pad blocks round-trip to exact zeros, so the
    contractions are unaffected and only ``nbytes`` prices the (small)
    alignment overhead.  ``nbytes`` prices the *shared* basis once, which is
    exactly the traffic amortization block-GMRES buys: one stored row serves
    all ``p`` right-hand sides.

    The two hot contractions generalize the accessor's ``dots``/``combine``
    and dispatch through the :class:`StorageFormat` protocol (so FRSZ2
    routes them through the fused decode-inside-contraction kernels under
    ``use_kernels``, mixed stores split head/tail, and sharded stores
    reduce partials over their mesh axis):

      * ``block_dots(store, W)``   — ``H[i,a,b] = <V[i,a], W[b]>``;
      * ``block_combine(store, Y)`` — ``out[b] = sum_{i,a} Y[i,a,b] V[i,a]``.

    Masking (the only accessor-level concern, as for the scalar accessor)
    is applied here — after the format's ``block_dots`` and before its
    ``block_combine`` — so fused kernel paths see unmasked inputs.
    """

    fmt: Any
    m: int                      # block-row capacity (solver passes m+1)
    p: int                      # block width = number of right-hand sides
    n: int                      # vector length (local chunk when sharded)
    arith_dtype: Any = jnp.float64

    @property
    def n_seg(self) -> int:
        """Aligned per-RHS segment length inside one flattened row."""
        a = self.fmt.block_align()
        return -(-self.n // a) * a

    @property
    def n_flat(self) -> int:
        return self.p * self.n_seg

    def empty(self):
        return self.fmt.empty(self.m, self.n_flat)

    def _pad_seg(self, W):
        if self.n_seg == self.n:
            return W
        return jnp.pad(W, ((0, 0), (0, self.n_seg - self.n)))

    def write_block(self, store, j, W):
        """Store block row j from ``W (p, n)`` (compress)."""
        return self.fmt.write_row(store, j,
                                  self._pad_seg(W).reshape(self.n_flat))

    def read_block(self, store, j):
        """Decompress block row j back to ``(p, n)``."""
        v = self.fmt.read_row(store, j, self.arith_dtype, self.n_flat)
        return v.reshape(self.p, self.n_seg)[:, : self.n]

    def read_all_blocks(self, store):
        V = self.fmt.read_all(store, self.arith_dtype, self.n_flat)
        return V.reshape(self.m, self.p, self.n_seg)[..., : self.n]

    # -- hot loops ------------------------------------------------------------
    def block_dots(self, store, W, row_mask=None):
        """``H[i, a, b] = <V[i, a], W[b]>`` with masked block rows zeroed."""
        H = self.fmt.block_dots(store, W, self.arith_dtype, self.n, self.p,
                                self.n_seg).astype(self.arith_dtype)
        if row_mask is not None:
            H = jnp.where(row_mask[:, None, None], H, 0.0)
        return H

    def block_combine(self, store, Y, row_mask=None):
        """``out[b] = sum_{i,a} Y[i, a, b] V[i, a]`` (local chunk when
        sharded — no collective, mirroring scalar ``combine``)."""
        if row_mask is not None:
            Y = jnp.where(row_mask[:, None, None], Y, 0.0)
        out = self.fmt.block_combine(store, Y, self.arith_dtype, self.n,
                                     self.p, self.n_seg)
        return out.astype(self.arith_dtype)[:, : self.n]

    def nbytes(self) -> int:
        return self.fmt.nbytes(self.m, self.n_flat)


# ---------------------------------------------------------------------------
# Registry (benchmarks / CLI select formats by name)
# ---------------------------------------------------------------------------

#: One table: exact names ("float64") and family prefixes ("frsz2", "mixed",
#: "emul") map to builders ``(name, *, arith_dtype, bs, use_kernels,
#: rounding) -> StorageFormat``.  ``format_by_name`` consults nothing else.
FORMATS: dict[str, Callable[..., StorageFormat]] = {}


def register_format(key: str):
    """Register a format builder under an exact name or family prefix."""

    def deco(builder):
        FORMATS[key] = builder
        return builder

    return deco


def _native_builder(dtype):
    def build(name, **ctx):
        return NativeFormat(dtype=dtype)

    return build


for _dt in (jnp.float64, jnp.float32, jnp.float16, jnp.bfloat16):
    register_format(jnp.dtype(_dt).name)(_native_builder(_dt))


@register_format("frsz2")
def _build_frsz2(name, *, arith_dtype=jnp.float64, bs=32, use_kernels=False,
                 rounding="truncate", **ctx):
    # "frsz2_<bits>", e.g. "frsz2_16" / "frsz2_21" / "frsz2_32"
    parts = name.split("_")
    if len(parts) != 2 or not parts[1].isdigit():
        raise ValueError(
            f"malformed frsz2 format name {name!r}: expected "
            "'frsz2_<bits>' (e.g. 'frsz2_16', 'frsz2_32')")
    l = int(parts[1])
    if not 1 <= l <= 64:
        raise ValueError(
            f"frsz2 code length must be in [1, 64], got {l} ({name!r})")
    spec = F.FrszSpec(bs=bs, l=l, dtype=arith_dtype, rounding=rounding)
    return FrszFormat(spec=spec, use_kernels=use_kernels)


def auto_mixed_head(tail_eps: float, target_rrn: float | None = None,
                    m: int | None = None) -> int:
    """Head size ``k`` for ``mixed:auto:<tail>`` from the solve's target.

    Inexact-Krylov coefficient-decay model: in the deciding restart cycle
    the least-squares coefficient of basis row ``j`` shrinks roughly
    geometrically from ``O(1)`` to ``O(target)`` over the ``m`` slots,
    ``c_j ~ target^(j/m)``.  Row ``j``'s storage error perturbs the
    correction by ``~c_j * eps_tail``, so the tail format is admissible
    once ``c_j * eps_tail <= 0.5 * target`` — the head must cover the rows
    before that, i.e. ``k = ceil(m * log(0.5*target/eps_tail)/log(target))``
    (clamped to ``[0, m]``; ``k = 0`` when the tail is already accurate
    enough for every row).  The same safety factor and epsilon contract as
    :meth:`repro.solver.pipeline.AdaptivePolicy.from_target` — the last
    hand-tuned head constant now derives from the target like the adaptive
    thresholds do.

    ``target_rrn``/``m`` are threaded through ``format_by_name`` by the
    solvers; direct registry lookups without them fall back to a 1e-12
    target over an m=100 basis (documented, deterministic).
    """
    import math

    tgt = 1e-12 if target_rrn is None else float(target_rrn)
    cap = 100 if m is None else int(m)
    if cap <= 0:
        return 0
    tgt = min(max(tgt, 1e-300), 0.5)      # log(tgt) < 0 needed below
    if float(tail_eps) <= 0.5 * tgt:
        return 0
    frac = math.log(0.5 * tgt / float(tail_eps)) / math.log(tgt)
    return max(0, min(cap, math.ceil(cap * min(frac, 1.0))))


@register_format("mixed")
def _build_mixed(name, *, arith_dtype=jnp.float64, target_rrn=None, m=None,
                 **ctx):
    # "mixed" | "mixed:<k>" | "mixed:auto" | "mixed:<k|auto>:<tail-name>"
    parts = name.split(":", 2)
    head_spec = parts[1] if len(parts) > 1 and parts[1] else "2"
    if head_spec != "auto" and not head_spec.isdigit():
        raise ValueError(
            f"malformed mixed format name {name!r}: the head size must be "
            "an integer or 'auto' ('mixed:<k|auto>[:<tail>]', e.g. "
            "'mixed:2:frsz2_32', 'mixed:auto:frsz2_16')")
    tail_name = parts[2] if len(parts) > 2 else "frsz2_32"
    tail = format_by_name(tail_name, arith_dtype=arith_dtype,
                          target_rrn=target_rrn, m=m, **ctx)
    k = (auto_mixed_head(tail.eps(), target_rrn, m)
         if head_spec == "auto" else int(head_spec))
    return MixedFormat(k=k, head=NativeFormat(arith_dtype), tail=tail)


@register_format("sharded")
def _build_sharded(name, *, axis_name="basis", compressed_transport=True,
                   **ctx):
    # "sharded:<inner-format-name>"
    inner_name = name.partition(":")[2]
    if not inner_name:
        raise ValueError("sharded format needs an inner format: "
                         "'sharded:<fmt>'")
    if inner_name.split(":", 1)[0] == "sharded":
        raise ValueError(
            f"nested sharded format {name!r} is not supported: the basis "
            "splits over exactly one mesh axis ('sharded:<fmt>')")
    inner = format_by_name(inner_name, **ctx)
    return ShardedFormat(inner=inner, axis_name=axis_name,
                         compressed_transport=compressed_transport)


@register_format("emul")
def _build_emul(name, **ctx):
    from repro.core.emulators import emulator_by_name

    return emulator_by_name(name.partition(":")[2])


def format_by_name(name: str, *, arith_dtype=None, bs: int = 32,
                   use_kernels: bool = False, rounding: str = "truncate",
                   target_rrn: float | None = None, m: int | None = None):
    """Resolve a storage format from the :data:`FORMATS` table.

    Exact names first ('float64', …), then family prefixes: 'frsz2_XX',
    'mixed[:k|auto[:tail]]', 'emul:…'.  ``arith_dtype`` defaults to the
    backend's (:func:`repro.runtime.arith_dtype`).  ``target_rrn``/``m``
    are solve context for self-sizing formats (``mixed:auto`` derives its
    head size from them); the solvers thread their arguments through
    automatically.
    """
    if arith_dtype is None:
        arith_dtype = runtime.arith_dtype()
    ctx = dict(arith_dtype=arith_dtype, bs=bs, use_kernels=use_kernels,
               rounding=rounding, target_rrn=target_rrn, m=m)
    if name in FORMATS:
        return FORMATS[name](name, **ctx)
    for sep in (":", "_"):
        family = name.split(sep)[0]
        if family != name and family in FORMATS:
            return FORMATS[family](name, **ctx)
    raise ValueError(f"unknown storage format {name!r}")
