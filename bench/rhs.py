"""The traffic generator: the right-hand side a cell solves.

Each traffic file (``bench/traffic/<name>.json``) gives the basis format the
solver stores its Krylov vectors in.  The right-hand side is drawn from the
seed: ``x_sol`` standard normal, normalised, and ``b = A x_sol`` in float64
on the host, cast to the arithmetic dtype.  The same seed gives the same
right-hand side.
"""
from __future__ import annotations

import numpy as np

from reference import matvec64


def make_rhs(seed: int, op, dtype):
    """``(x_sol, b)``: ``x_sol`` float64, ``b`` in ``dtype``; ``op`` is the
    operator's ``(indptr, indices, data)``."""
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    x = np.random.default_rng(seed).standard_normal(len(op[0]) - 1)
    x /= np.linalg.norm(x)
    return x, matvec64(*op, x).astype(dtype)
