"""Sparse matrix formats and SpMV in pure JAX.

Two formats:

* :class:`CSR` — the assembly/IO format; SpMV via ``segment_sum`` (CPU-friendly,
  used by the f64 paper-faithful solver runs).
* :class:`ELL` — fixed row width, SpMV via gather + dense reduce.  This is the
  TPU-friendly layout (regular access, no data-dependent control flow) that
  the distributed solver shards row-wise.

Both are registered pytrees so they pass through jit / shard_map.
"""
from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CSR", "ELL", "csr_from_coo"]


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
