"""The plain reference: the configuration's operator built in numpy from its
stated stencil, its content hash, and the true residual in float64.

Nothing here imports the program.  The operator comes from the stencil that
the configuration file states (grid and coefficients), so the reference
defines the problem on its own; the program's operator has to hash to the
same digest, and so has the reference's, as recorded in the configuration.
"""
from __future__ import annotations

import hashlib

import numpy as np


def stencil_csr(config: dict, grid=None):
    """Canonical CSR ``(indptr, indices, data)`` of the configuration's
    stencil operator: rows in lexicographic grid order, columns ascending
    within a row, values in the configuration's arithmetic dtype.

    ``grid`` overrides the configuration's grid (small sizes for tests).
    """
    st = config["stencil"]
    nx, ny, nz = grid or st["grid"]
    dtype = np.dtype(config["arithmetic"])
    terms = [(0, 0, 0, st["diagonal"])] + [tuple(t) for t in st["neighbors"]]
    # ascending linear offset = ascending column within every row
    terms.sort(key=lambda t: t[0] * ny * nz + t[1] * nz + t[2])
    n = nx * ny * nz
    i, j, k = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                          indexing="ij")
    i, j, k = i.ravel(), j.ravel(), k.ravel()
    cols = np.empty((n, len(terms)), np.int64)
    vals = np.empty((n, len(terms)), dtype)
    live = np.empty((n, len(terms)), bool)
    for t, (dx, dy, dz, coeff) in enumerate(terms):
        ii, jj, kk = i + dx, j + dy, k + dz
        live[:, t] = ((ii >= 0) & (ii < nx) & (jj >= 0) & (jj < ny)
                      & (kk >= 0) & (kk < nz))
        cols[:, t] = (ii * ny + jj) * nz + kk
        vals[:, t] = coeff
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(live.sum(axis=1), out=indptr[1:])
    return indptr, cols[live], vals[live]


def canonical(indptr, indices, data):
    """The same matrix with columns ascending within each row."""
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices, np.int64)
    data = np.asarray(data)
    n = indptr.size - 1
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    key = rows * n + indices
    if key.size > 1 and not np.all(key[1:] > key[:-1]):
        order = np.argsort(key, kind="stable")
        indices, data = indices[order], data[order]
    return indptr, indices, data


def operator_sha256(indptr, indices, data) -> str:
    """Content hash of a CSR operator, independent of the order of the
    entries within a row and of the index dtype."""
    indptr, indices, data = canonical(indptr, indices, data)
    h = hashlib.sha256(f"csr n={indptr.size - 1} {data.dtype.name}".encode())
    for a in (indptr, indices, data):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def matvec64(indptr, indices, data, x):
    """``A @ x`` in float64."""
    indptr = np.asarray(indptr, np.int64)
    rows = np.repeat(np.arange(indptr.size - 1), np.diff(indptr))
    prod = np.asarray(data, np.float64) * np.asarray(x, np.float64)[
        np.asarray(indices)]
    return np.bincount(rows, weights=prod, minlength=indptr.size - 1)


def true_rrn(op, b, x) -> float:
    """``||b - A x|| / ||b||`` in float64; ``op`` is ``(indptr, indices,
    data)``."""
    b = np.asarray(b, np.float64)
    r = b - matvec64(*op, x)
    return float(np.linalg.norm(r) / np.linalg.norm(b))
