"""Sparse matrix formats and SpMV in pure JAX.

Three formats:

* :class:`CSR` — the assembly/IO format, and the operator the solvers take;
  its own SpMV gathers ``x`` per nonzero and sums rows with a
  ``segment_sum`` scatter-add.  Any sparsity works, but a TPU is slowest at
  exactly those two steps.
* :class:`DIA` — banded operators stored by diagonal; SpMV is a sum of
  products with statically shifted slices of ``x``: no gather, no scatter.
  :meth:`CSR.to_dia` converts where the structure suits, and
  :func:`operator_matvec` picks this path whenever it does.
* :class:`ELL` — fixed row width, SpMV via gather + dense reduce; the
  layout the sharded driver partitions row-wise.

All are registered pytrees so they pass through jit / shard_map.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.tree_util import Partial

__all__ = ["CSR", "DIA", "ELL", "csr_from_coo", "operator_matvec"]

#: a CSR converts to DIA when it has at most this many distinct diagonals
DIA_MAX_OFFSETS = 64
#: ... and storing them whole costs at most this many values per nonzero
DIA_MAX_FILL = 1.25


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CSR:
    """Compressed sparse row.  ``indptr`` (n+1,), ``indices``/``data`` (nnz,)."""

    indptr: jax.Array
    indices: jax.Array
    data: jax.Array
    shape: tuple

    def tree_flatten(self):
        return (self.indptr, self.indices, self.data), (self.shape,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        indptr, indices, data = children
        return cls(indptr, indices, data, aux[0])

    @property
    def nnz(self) -> int:
        return self.data.shape[0]

    @property
    def dtype(self):
        return self.data.dtype

    def row_ids(self) -> jax.Array:
        """(nnz,) row index per entry — precomputed once, reused by SpMV."""
        n = self.shape[0]
        return jnp.cumsum(
            jnp.zeros(self.nnz, jnp.int32).at[self.indptr[1:-1]].add(1)
        )

    def matvec(self, x: jax.Array, row_ids: jax.Array | None = None) -> jax.Array:
        with jax.named_scope("spmv"):
            if row_ids is None:
                row_ids = self.row_ids()
            prod = self.data * x[self.indices].astype(self.data.dtype)
            return jax.ops.segment_sum(prod, row_ids, num_segments=self.shape[0])

    def diag(self) -> jax.Array:
        """(n,) main diagonal (zeros where a row has no diagonal entry)."""
        row_ids = self.row_ids()
        on_diag = self.indices == row_ids
        return jax.ops.segment_sum(
            jnp.where(on_diag, self.data, 0.0), row_ids,
            num_segments=self.shape[0])

    def fingerprint(self) -> str:
        """Content hash of (shape, structure, values) — stable across
        rebuilds of the same matrix, used by the compiled-solve cache."""
        fp = getattr(self, "_fingerprint", None)
        if fp is None:
            h = hashlib.sha1(repr(self.shape).encode())
            for a in (self.indptr, self.indices, self.data):
                h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
            fp = self._fingerprint = h.hexdigest()
        return fp

    def bandwidth(self) -> int:
        """max |col - row| over nonzero entries (host-side, cached).

        Explicitly-stored zeros are excluded: they contribute nothing to a
        matvec, so the halo partitioner may ignore their columns.
        """
        bw = getattr(self, "_bandwidth", None)
        if bw is None:
            indptr = np.asarray(self.indptr)
            rows = np.repeat(np.arange(self.shape[0]), np.diff(indptr))
            live = np.asarray(self.data) != 0
            off = np.abs(np.asarray(self.indices)[live] - rows[live])
            bw = self._bandwidth = int(off.max()) if off.size else 0
        return bw

    def nbytes(self) -> int:
        """Bytes one full SpMV streams from the operator: values, column
        indices, and the row pointer — the A-traffic term of the paper's
        bandwidth model (the basis terms come from the storage formats)."""
        return int(self.data.size * self.data.dtype.itemsize
                   + self.indices.size * self.indices.dtype.itemsize
                   + self.indptr.size * self.indptr.dtype.itemsize)

    def __matmul__(self, x):
        return self.matvec(x)

    def to_ell(self, width: int | None = None) -> ELL:
        indptr = np.asarray(self.indptr)
        indices = np.asarray(self.indices)
        data = np.asarray(self.data)
        n = self.shape[0]
        counts = np.diff(indptr)
        w = int(counts.max()) if width is None else width
        cols = np.zeros((n, w), np.int32)
        vals = np.zeros((n, w), data.dtype)
        for i in range(n):
            c = counts[i]
            cols[i, :c] = indices[indptr[i]:indptr[i] + c]
            vals[i, :c] = data[indptr[i]:indptr[i] + c]
        return ELL(jnp.asarray(cols), jnp.asarray(vals), self.shape)

    def to_dense(self) -> jax.Array:
        d = jnp.zeros(self.shape, self.data.dtype)
        return d.at[self.row_ids(), self.indices].add(self.data)

    def to_dia(self) -> DIA | None:
        """This operator stored by diagonal (host-side, cached), or ``None``
        where its structure does not suit: more than
        :data:`DIA_MAX_OFFSETS` distinct diagonals, or more than
        :data:`DIA_MAX_FILL` stored values per nonzero."""
        if not hasattr(self, "_dia"):
            self._dia = _dia_from_csr(self)
        return self._dia


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DIA:
    """Diagonal storage of a square operator.

    ``offsets`` is a static tuple of ``K`` distinct column-minus-row
    offsets; ``vals`` holds one ``(n,)`` array per offset, with
    ``vals[k][i] = A[i, i + offsets[k]]``, 0 where row ``i`` stores nothing
    on that diagonal.  A tuple of rows and not one ``(K, n)`` array: a TPU
    tiles a 2-D array by (8, 128), so a row of it is strided and XLA copies
    it out before every use, where a 1-D array is read in place.
    """

    offsets: tuple
    vals: tuple
    shape: tuple

    def tree_flatten(self):
        return (self.vals,), (self.offsets, self.shape)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], tuple(children[0]), aux[1])

    def matvec(self, x: jax.Array) -> jax.Array:
        """``y = A @ x`` as ``sum_k vals[k] * x[i + offsets[k]]``, with ``x``
        padded by zeros so that every shifted slice is static.  Jitted, so
        that a call outside a solve rounds as the same SpMV inside one."""
        return _dia_program(self.offsets, self.shape[0])(self.vals, x)


@functools.lru_cache(maxsize=64)
def _dia_program(offsets: tuple, n: int):
    """The jitted SpMV of a DIA structure, one per ``(offsets, n)``."""
    lo = max(0, -min(offsets))
    hi = max(0, max(offsets))

    @jax.jit
    def apply(vals, x):
        with jax.named_scope("spmv"), jax.named_scope("dia"):
            xp = jnp.pad(x.astype(vals[0].dtype), (lo, hi))
            y = vals[0] * xp[lo + offsets[0]:lo + offsets[0] + n]
            for k in range(1, len(offsets)):
                off = lo + offsets[k]
                y = y + vals[k] * xp[off:off + n]
            return y

    return apply


def _dia_from_csr(A: CSR) -> DIA | None:
    """:meth:`CSR.to_dia`, uncached: O(nnz + K n) numpy, no loop over rows."""
    n = A.shape[0]
    indptr = np.asarray(A.indptr)
    nnz = int(indptr[-1])
    if A.shape[1] != n or nnz == 0:
        return None
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    off = np.asarray(A.indices)[:nnz].astype(np.int64) - rows
    present = np.bincount(off + (n - 1), minlength=2 * n - 1)
    offsets = np.flatnonzero(present) - (n - 1)
    K = offsets.size
    if K > DIA_MAX_OFFSETS or K * n > DIA_MAX_FILL * nnz:
        return None
    slot = np.zeros(2 * n - 1, np.int64)
    slot[offsets + (n - 1)] = np.arange(K)
    flat = slot[off + (n - 1)] * n + rows
    seen = np.zeros(K * n, bool)
    seen[flat] = True
    if np.count_nonzero(seen) < nnz:
        return None              # duplicate entries: CSR sums them
    data = np.asarray(A.data)[:nnz]
    vals = np.zeros(K * n, data.dtype)
    vals[flat] = data
    return DIA(tuple(int(o) for o in offsets),
               tuple(jnp.asarray(v) for v in vals.reshape(K, n)), (n, n))


def operator_matvec(A):
    """The SpMV the solvers run for operator ``A``: the DIA path where
    :meth:`CSR.to_dia` converts ``A``, else ``A``'s own matvec (a CSR's
    with its ``row_ids`` computed once).  A pytree, so a sharded solve can
    pass it into ``shard_map`` as an operand."""
    if isinstance(A, CSR):
        dia = A.to_dia()
        if dia is not None:
            return Partial(DIA.matvec, dia)
        return Partial(CSR.matvec, A, row_ids=A.row_ids())
    return Partial(_own_matvec, A)


def _own_matvec(A, x):
    return A.matvec(x)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ELL:
    """ELLPACK: ``cols``/``vals`` (n, width); padding has val 0, col 0."""

    cols: jax.Array
    vals: jax.Array
    shape: tuple

    def tree_flatten(self):
        return (self.cols, self.vals), (self.shape,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        cols, vals = children
        return cls(cols, vals, aux[0])

    @property
    def dtype(self):
        return self.vals.dtype

    def matvec(self, x: jax.Array) -> jax.Array:
        """y = A @ x (XLA gather).  ``x`` may also be an FRSZ2
        ``BlockCompressed`` operand, decompressed first."""
        from repro.core import frsz2 as F

        with jax.named_scope("spmv"):
            if isinstance(x, F.BlockCompressed):
                x = F.decompress(x)
            return (self.vals * x[self.cols].astype(self.vals.dtype)).sum(axis=1)

    def diag(self) -> jax.Array:
        """(n,) main diagonal (padding slots carry val 0, so they drop out)."""
        n = self.shape[0]
        on_diag = self.cols == jnp.arange(n)[:, None]
        return jnp.where(on_diag, self.vals, 0.0).sum(axis=1)

    def fingerprint(self) -> str:
        """Content hash, see :meth:`CSR.fingerprint`."""
        fp = getattr(self, "_fingerprint", None)
        if fp is None:
            h = hashlib.sha1(repr(self.shape).encode())
            for a in (self.cols, self.vals):
                h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
            fp = self._fingerprint = h.hexdigest()
        return fp

    def bandwidth(self) -> int:
        """max |col - row| over nonzero entries (host-side, cached).

        Padding slots carry val 0 / col 0, so masking on the values also
        keeps a high row's padding from faking an (n-ish) bandwidth.
        """
        bw = getattr(self, "_bandwidth", None)
        if bw is None:
            live = np.asarray(self.vals) != 0
            rows = np.arange(self.shape[0])[:, None]
            off = np.abs(np.asarray(self.cols) - rows)[live]
            bw = self._bandwidth = int(off.max()) if off.size else 0
        return bw

    def nbytes(self) -> int:
        """Bytes one full SpMV streams: padded values + column indices
        (see :meth:`CSR.nbytes`; ELL has no row pointer)."""
        return int(self.vals.size * self.vals.dtype.itemsize
                   + self.cols.size * self.cols.dtype.itemsize)

    def __matmul__(self, x):
        return self.matvec(x)


def csr_from_coo(rows, cols, vals, shape) -> CSR:
    """Build CSR from (unsorted, duplicate-free) COO triplets on host."""
    rows = np.asarray(rows)
    order = np.argsort(rows, kind="stable")
    rows, cols, vals = rows[order], np.asarray(cols)[order], np.asarray(vals)[order]
    indptr = np.zeros(shape[0] + 1, np.int32)
    np.add.at(indptr, rows + 1, 1)
    indptr = np.cumsum(indptr)
    return CSR(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(cols, jnp.int32),
        data=jnp.asarray(vals),
        shape=tuple(shape),
    )
