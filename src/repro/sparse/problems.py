"""Synthetic CFD-like test problems (offline stand-ins for SuiteSparse).

The paper benchmarks SuiteSparse CFD matrices (Table I).  That collection is
unavailable in this offline container, so we generate problems with the same
*numerical character*, at configurable size:

* ``synth:atmosmod``    — 3-D convection-diffusion 7-point stencil
  (nonsymmetric, like atmosmodd/j/l/m: atmospheric modelling).
* ``synth:aniso2d``     — 2-D anisotropic diffusion 5-point stencil
  (parabolic_fem-like; SPD-ish but we treat it with GMRES regardless).
* ``synth:lung``        — 1-D-coupled transport chain, strongly nonsymmetric,
  diagonally dominant (lung2-like).
* ``synth:widerange``   — convection-diffusion with row/column scaling drawn
  from a log-uniform distribution spanning ~80 binary orders of magnitude.
  This reproduces the **PR02R pathology** (paper Fig. 10: exponents from
  -178 to 36): FRSZ2 blocks see a huge in-block exponent spread and lose
  the small-magnitude components to the normalization shift.
* ``synth:varcoef``     — row-scaled convection-diffusion (variable
  coefficients): the diagonal spans ~12 binary orders, so Jacobi
  preconditioning is decisive (the preconditioner-hook showcase).
* ``synth:stretched``   — mildly stretched-grid convection-diffusion
  (StocF-1465-like, moderate conditioning).
* ``synth:stencil27``   — 27-point stencil on a cube: wide-but-local band
  (the sharded halo-SpMV workload).
* ``synth:unstructured``— randomly row/col-permuted 27-point stencil on an
  elongated grid: raw bandwidth ~n, so the sharded matvec falls back to
  the gathered path until an RCM reordering restores the band (the
  operator-planning showcase).

Every generator returns ``(CSR, name)`` with a deterministic layout; the
right-hand side convention follows the paper (Sec. V-B): ``x_sol = s/||s||``
with ``s[i] = sin(i)``, ``b = A x_sol``, ``x0 = 0``.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.sparse.csr import CSR, csr_from_coo, csr_from_counts

__all__ = ["make_problem", "rhs_for", "PROBLEMS", "problem_suite"]


def _stencil3d(nx, ny, nz, wind=(0.4, 0.2, 0.1), diff=1.0,
               dtype=np.float64) -> CSR:
    """7-point convection-diffusion stencil on an nx×ny×nz grid, as a CSR.

    Central differences for diffusion + upwind for convection gives a
    nonsymmetric M-matrix — the atmosmod family character.  Each row holds
    its diagonal, then its ±x, ±y, ±z neighbours that exist, in that
    order; built row by row in one pass (no sort), which a 38M-row grid
    needs.
    """
    n = nx * ny * nz
    shape = (nx, ny, nz)
    diag = 6.0 * diff + sum(abs(w) for w in wind)
    terms = [((0, 0, 0), diag)]
    # ± x/y/z neighbours with upwind-biased convection
    for axis, w in zip(range(3), wind):
        for sgn in (+1, -1):
            d = [0, 0, 0]
            d[axis] = sgn
            off = -diff + (-w if sgn > 0 else 0.0) + (w if sgn < 0 else 0.0)
            # upwind: the coefficient against the wind is strengthened
            terms.append((tuple(d), off - 0.05 * sgn * w))
    idx = np.arange(n, dtype=np.int64).reshape(shape)
    cols = np.empty(shape + (len(terms),), np.int64)
    live = np.zeros(shape + (len(terms),), bool)
    for t, (d, _) in enumerate(terms):
        has = tuple(slice(max(0, -dk), s - max(0, dk))
                    for dk, s in zip(d, shape))
        live[has + (t,)] = True
        cols[..., t] = idx + (d[0] * ny + d[1]) * nz + d[2]
    del idx
    live = live.reshape(n, len(terms))
    vals = np.array([v for _, v in terms], dtype)
    return csr_from_counts(live.sum(axis=1), cols.reshape(n, -1)[live],
                           np.broadcast_to(vals, live.shape)[live], (n, n))


def _problem_atmosmod(n_target: int, dtype=np.float64) -> CSR:
    s = max(4, round(n_target ** (1 / 3)))
    A = _stencil3d(s, s, s, dtype=dtype)
    # cell geometry for the 3-D block partitioner (a plain attribute:
    # dropped by pytree round-trips and permute_csr, which is correct —
    # a permuted operator has lost its lexicographic meaning)
    A.grid = (s, s, s)
    return A


def _problem_aniso2d(n_target: int, dtype=np.float64) -> CSR:
    s = max(4, round(n_target ** 0.5))
    n = s * s
    idx = np.arange(n).reshape(s, s)
    eps = 1e-3  # anisotropy ratio
    rows, cols, vals = [], [], []

    def add(r, c, v):
        rows.append(r.ravel()); cols.append(c.ravel())
        vals.append(np.full(r.size, v, dtype))

    add(idx, idx, 2.0 + 2.0 * eps)
    add(idx[1:, :], idx[:-1, :], -1.0)
    add(idx[:-1, :], idx[1:, :], -1.0)
    add(idx[:, 1:], idx[:, :-1], -eps)
    add(idx[:, :-1], idx[:, 1:], -eps)
    A = csr_from_coo(
        np.concatenate(rows), np.concatenate(cols), np.concatenate(vals), (n, n)
    )
    A.grid = (s, s, 1)   # 2-D degenerate case of the block partitioner
    return A


def _problem_lung(n_target: int, dtype=np.float64) -> CSR:
    n = max(16, n_target)
    i = np.arange(n)
    rows = np.concatenate([i, i[1:], i[:-1], i[: n - 7]])
    cols = np.concatenate([i, i[:-1], i[1:], i[7:] if n > 7 else i[:0]])
    rng = np.random.default_rng(7)
    vals = np.concatenate([
        np.full(n, 4.0, dtype),
        np.full(n - 1, -1.7, dtype),          # strong lower coupling
        np.full(n - 1, -0.3, dtype),          # weak upper coupling
        rng.uniform(-0.2, 0.2, max(n - 7, 0)).astype(dtype),
    ])
    return csr_from_coo(rows, cols, vals, (n, n))


def _problem_widerange(n_target: int, dtype=np.float64,
                       orders: int = 14) -> CSR:
    """PR02R-like (paper Fig. 9b/10): similarity scaling D·A0·D^-1 with
    D = 2^U(-orders, orders).

    The spectrum stays the nice convection-diffusion one (f64 GMRES
    converges fast), but every Krylov vector carries the permanent
    per-coordinate scaling D — wide in-block exponent spread — which is
    precisely the regime where a block-shared-exponent format loses the
    small coordinates to the normalization shift while *per-value* formats
    (float32) are unaffected.  Empirically (n=512, orders=14): f64
    converges in ~35 iterations, float32 in ~52, frsz2_32 stalls at
    ~3e-8 — the paper's PR02R story.
    """
    base = _problem_atmosmod(n_target, dtype)
    n = base.shape[0]
    rng = np.random.default_rng(42)
    d = np.exp2(rng.uniform(-orders, orders, n)).astype(dtype)
    indptr = np.asarray(base.indptr)
    idx = np.asarray(base.indices)
    row_ids = np.repeat(np.arange(n), np.diff(indptr))
    data = np.asarray(base.data) * d[row_ids] / d[idx]
    A = CSR(base.indptr, base.indices, jnp.asarray(data), base.shape)
    A.grid = base.grid   # scaling preserves the stencil's cell layout
    return A


def _problem_varcoef(n_target: int, dtype=np.float64, orders: int = 6) -> CSR:
    """Variable-coefficient convection-diffusion: row scaling D·A0 with
    D = 2^U(-orders, orders).

    Unlike ``synth:widerange`` (a *similarity* transform, which leaves the
    diagonal constant), plain row scaling models a variable-coefficient /
    badly-nondimensionalized PDE: the diagonal varies over ~2*orders binary
    orders of magnitude.  Unpreconditioned GMRES crawls (the row imbalance
    spreads the spectrum); Jacobi right preconditioning ``A diag(A)^{-1}``
    collapses it back to a similarity transform of the well-conditioned
    stencil and converges in a handful of iterations — the canonical
    preconditioner-hook demonstration (empirically at n=512: ~1160
    iterations unpreconditioned vs ~35 with Jacobi).
    """
    base = _problem_atmosmod(n_target, dtype)
    n = base.shape[0]
    rng = np.random.default_rng(11)
    d = np.exp2(rng.uniform(-orders, orders, n)).astype(dtype)
    indptr = np.asarray(base.indptr)
    row_ids = np.repeat(np.arange(n), np.diff(indptr))
    data = np.asarray(base.data) * d[row_ids]
    A = CSR(base.indptr, base.indices, jnp.asarray(data), base.shape)
    A.grid = base.grid   # row scaling preserves the stencil's cell layout
    return A


def _stencil27_box(nx: int, ny: int, nz: int, dtype=np.float64) -> CSR:
    """27-point convection-diffusion stencil on an nx×ny×nz grid.

    All 26 neighbors of the {-1, 0, 1}³ cube couple (face/edge/corner
    weights 1 / 0.5 / 0.25, upwind-perturbed for nonsymmetry) under a
    strictly dominant diagonal.  Lexicographic ordering gives bandwidth
    ny·nz + nz + 1.
    """
    n = nx * ny * nz
    idx = np.arange(n).reshape(nx, ny, nz)
    wind = (0.4, 0.2, 0.1)
    rows, cols, vals = [], [], []

    def add(r, c, v):
        rows.append(r.ravel())
        cols.append(c.ravel())
        vals.append(np.full(r.size, v, dtype))

    total_off = 0.0
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dz in (-1, 0, 1):
                if dx == dy == dz == 0:
                    continue
                dist = abs(dx) + abs(dy) + abs(dz)
                base = {1: 1.0, 2: 0.5, 3: 0.25}[dist]
                # upwind bias: downwind couplings weaken, upwind strengthen
                coeff = -base - 0.1 * (dx * wind[0] + dy * wind[1]
                                       + dz * wind[2])
                total_off += abs(coeff)
                sl_src, sl_dst = [], []
                for d in (dx, dy, dz):
                    if d > 0:
                        sl_src.append(slice(0, -1))
                        sl_dst.append(slice(1, None))
                    elif d < 0:
                        sl_src.append(slice(1, None))
                        sl_dst.append(slice(0, -1))
                    else:
                        sl_src.append(slice(None))
                        sl_dst.append(slice(None))
                add(idx[tuple(sl_src)], idx[tuple(sl_dst)], coeff)
    add(idx, idx, 1.05 * total_off)
    return csr_from_coo(
        np.concatenate(rows), np.concatenate(cols), np.concatenate(vals),
        (n, n),
    )


def _problem_stencil27(n_target: int, dtype=np.float64) -> CSR:
    """27-point stencil on an s×s×s cube (see :func:`_stencil27_box`).

    Numerically tame; its purpose is the *column structure*: lexicographic
    ordering gives bandwidth s² + s + 1, a wide-but-still-local band — the
    canonical workload for the sharded driver's neighbor-exchange halo
    SpMV (vs the 7-point stencils, whose band is barely wider than one
    chunk at small n).
    """
    s = max(4, round(n_target ** (1 / 3)))
    A = _stencil27_box(s, s, s, dtype=dtype)
    A.grid = (s, s, s)
    return A


def _problem_unstructured(n_target: int, dtype=np.float64) -> CSR:
    """Randomly row/col-permuted 27-point stencil: the RCM showcase.

    A fixed random *symmetric* permutation of :func:`_stencil27_box` on an
    elongated (8s)×s×s grid — same spectrum and same per-row structure as
    the banded original (the permutation is a similarity transform), but
    the lexicographic locality is destroyed: raw column bandwidth is ~n,
    so the sharded matvec probe falls back to the gathered all-gather
    path.  Reverse Cuthill-McKee (``reorder="rcm"``/``"auto"``,
    :mod:`repro.sparse.reorder`) recovers a narrow band (≈ 2·s² on the
    elongated grid vs the lexicographic s² + s + 1) and unlocks the
    neighbor-exchange halo path — the ``benchmarks/shard_wire.py``
    demonstration.  The long thin domain is deliberate: it is the regime
    where a bandwidth-reducing ordering exists and is decisively narrower
    than the gather threshold at small test sizes (a cube's BFS level
    sets are ~3s², which leaves no headroom below n ≈ 10⁴).
    """
    s = max(4, round((n_target / 8) ** (1 / 3)))
    base = _stencil27_box(8 * s, s, s, dtype=dtype)
    from repro.sparse.reorder import permute_csr

    scramble = np.random.default_rng(5).permutation(base.shape[0])
    return permute_csr(base, scramble)


def _problem_stretched(n_target: int, dtype=np.float64) -> CSR:
    s = max(4, round(n_target ** (1 / 3)))
    A = _stencil3d(s, s, s, wind=(1.5, 0.0, 0.0), diff=0.3, dtype=dtype)
    A.grid = (s, s, s)
    return A


PROBLEMS = {
    "synth:atmosmod": (_problem_atmosmod, 4.0e-14),
    "synth:aniso2d": (_problem_aniso2d, 1.0e-12),
    "synth:lung": (_problem_lung, 1.0e-10),
    "synth:widerange": (_problem_widerange, 4.0e-03),
    "synth:varcoef": (_problem_varcoef, 1.0e-11),
    "synth:stretched": (_problem_stretched, 4.0e-06),
    "synth:stencil27": (_problem_stencil27, 1.0e-13),
    "synth:unstructured": (_problem_unstructured, 1.0e-13),
}


def make_problem(name: str, n: int = 8000, dtype=np.float64):
    """Returns (A: CSR, target_rrn: float).  Target RRNs mirror Table I's
    per-problem calibration (achievable accuracy + wiggle room)."""
    try:
        gen, rrn = PROBLEMS[name]
    except KeyError:
        raise ValueError(
            f"unknown problem {name!r}; available problems: "
            f"{', '.join(sorted(PROBLEMS))}") from None
    return gen(n, dtype=dtype), rrn


def rhs_for(A: CSR):
    """Paper Sec. V-B: x_sol = s/||s||, s[i] = sin(i); b = A @ x_sol."""
    n = A.shape[0]
    s = jnp.sin(jnp.arange(n, dtype=A.dtype))
    x_sol = s / jnp.linalg.norm(s)
    b = A.matvec(x_sol)
    return b, x_sol


def problem_suite(n: int = 8000):
    for name in PROBLEMS:
        A, rrn = make_problem(name, n)
        b, x_sol = rhs_for(A)
        yield name, A, b, x_sol, rrn
