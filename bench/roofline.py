"""The chip's peaks and the least bytes that the solver's work needs.

The byte counts are the algorithm's, not the program's: they count what
any implementation has to move, so that a better kernel reads a higher
share and no kernel can read over 100%.

* SpMV: the ``nnz`` stored values once, ``x`` read once and ``y`` written
  once, at the arithmetic width.  No index bytes, so a stencil or DIA SpMV
  is held to the same floor as CSR.  A solve of ``k`` iterations needs
  ``k`` Arnoldi SpMVs, one residual at the start and one at the end of each
  restart cycle, whose lengths come from the solve itself
  (``cycle_lengths``).
* Basis: at iteration ``j`` (0-based) of a cycle, one row written and
  ``j + 1`` rows read by the dot-product pass and ``j + 1`` by the combine
  pass, each row at the format's stored bits per value.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

#: values per shared exponent of the FRSZ2 block formats
FRSZ2_BLOCK = 32


def peak(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path.name} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def stored_bits(fmt: str) -> float:
    """Stored bits per basis value: the dtype's width for a native format,
    ``l`` plus a 32-bit exponent per block for ``frsz2_<l>``."""
    if fmt.startswith("frsz2_"):
        return int(fmt.split("_", 1)[1]) + 32 / FRSZ2_BLOCK
    return np.dtype(fmt).itemsize * 8


def cycle_lengths(history, target_rrn: float, m: int) -> list:
    """Lengths of the restart cycles of a solve, read from its implicit
    residual history (``GmresResult.rrn_history``, one estimate per
    iteration).  A cycle ends at its first estimate at or under the target,
    or after ``m`` iterations, as the restart driver ends it: a solve that
    restarts early shows as, say, 33 + 8, not as one cycle of 41."""
    hist = np.asarray(history)
    hit = hist <= np.asarray(target_rrn, hist.dtype)
    lengths, k = [], 0
    for h in hit:
        k += 1
        if h or k == m:
            lengths.append(k)
            k = 0
    return lengths + ([k] if k else [])


def spmv_calls(cycles) -> int:
    """SpMVs a solve of restart cycles of ``cycles`` iterations needs: one
    per iteration, plus the initial residual and one residual at the end
    of each cycle."""
    return sum(cycles) + len(cycles) + 1


def spmv_bytes(n: int, nnz: int, value_bytes: int) -> int:
    """Least bytes of one SpMV: values, ``x`` and ``y``, no indices."""
    return (nnz + 2 * n) * value_bytes


def basis_bytes(cycles, n: int, bits: float) -> float:
    """Least basis bytes of a solve of restart cycles of ``cycles``
    iterations: per iteration ``j`` of a cycle, one row written and
    ``2 (j + 1)`` rows read."""
    rows = sum(k + k * (k + 1) for k in cycles)
    return rows * n * bits / 8


def share_pct(least_bytes: float, seconds: float, bytes_per_s: float):
    """Least time over measured time, in %; ``None`` where nothing was
    measured."""
    if seconds <= 0 or least_bytes <= 0:
        return None
    return 100.0 * least_bytes / bytes_per_s / seconds


__all__ = ["peak", "stored_bits", "cycle_lengths", "spmv_calls", "spmv_bytes",
           "basis_bytes", "share_pct"]
