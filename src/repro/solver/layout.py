"""Where a solve runs: on one chip, or on every local chip.

:func:`repro.solver.gmres.solve_program` asks :func:`solve_chips` on every
call.  The answer comes from the input alone, with no option: the bytes a
one-chip solve holds against the device's memory limit.  A solve that fits
one chip runs there (the one-device program); one that does not runs on
every local chip, row-partitioned (:mod:`repro.solver.sharded`).

The bytes of a one-chip solve (:func:`solve_bytes`):

* the Krylov store, ``m + 1`` rows of ``n`` values at each storage
  format's bits, once per level of the precision policy, counted twice:
  room for one whole copy of it, which the program makes nowhere since
  each restart cycle allocates its own store
  (``tests/test_store_copies.py``), so the rule errs toward more chips;
* the operator as the SpMV reads it: its diagonals where it stores by
  diagonal, else the CSR and its row ids;
* :data:`VECTORS` vectors of ``n`` values at the arithmetic width.
"""
from __future__ import annotations

import numpy as np

__all__ = ["VECTORS", "bytes_limit", "layout_chips", "solve_bytes",
           "solve_chips"]

#: work vectors of ``n`` values a solve holds beside the store: ``b``,
#: ``x``, ``x0``, the residual, the new Krylov vector and the SpMV's
#: operand and result, with one to spare
VECTORS = 8


def solve_bytes(n: int, m: int, formats, operator_bytes: int,
                value_bytes: int) -> int:
    """Device bytes a one-chip solve of ``n`` rows holds: the store of
    ``m + 1`` rows in each of ``formats`` and room for a copy, the
    operator, and :data:`VECTORS` vectors of ``value_bytes`` values."""
    store = sum(f.nbytes(m + 1, n) for f in formats)
    return 2 * store + int(operator_bytes) + VECTORS * n * value_bytes


def layout_chips(need_bytes: int, limit: int | None, chips: int) -> int:
    """One chip where ``need_bytes`` fits under ``limit`` (``None``: no
    limit), else all ``chips``."""
    if limit is None or need_bytes <= limit:
        return 1
    return chips


def bytes_limit(device) -> int | None:
    """The memory limit the device reports, ``None`` where it reports none
    (the CPU backend)."""
    stats = device.memory_stats() or {}
    return stats.get("bytes_limit")


def _operator_bytes(A) -> int:
    """Device bytes of the SpMV's operator, a CSR: its diagonals, else its
    arrays and row ids."""
    dia = A.dia_arrays()
    return A.nbytes() + 4 * A.nnz if dia is None else dia[1].nbytes


def solve_chips(A, n: int, m: int, formats, arith_dtype) -> int:
    """How many of the local chips the solve of the CSR ``A`` runs on."""
    import jax

    devices = jax.local_devices()
    if len(devices) == 1:
        return 1
    need = solve_bytes(n, m, formats, _operator_bytes(A),
                       np.dtype(arith_dtype).itemsize)
    return layout_chips(need, bytes_limit(devices[0]), len(devices))
