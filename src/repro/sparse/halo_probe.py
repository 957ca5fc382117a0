"""Host-side partition probing: 1-D bandwidth halo and 3-D block geometry.

Two probes feed :mod:`repro.sparse.plan`'s matvec-mode arbitration, both
pure numpy over the operator's index arrays (the same setup-time tier as
RCM reordering and ELL conversion):

* :func:`halo_probe` — the 1-D row partition's column-bandwidth probe
  (PR 4): per-hop boundary *strips* whose total is the one-sided halo
  width.  On an s³ grid in lexicographic order the strip is O(s²) —
  the whole cross-section travels even though only the neighbors of the
  cut plane are referenced.

* :func:`block_partition` — the 3-D (with 2-D/1-D degenerate cases)
  **block** partition: the mesh axis ``P`` factors into a ``(Px, Py, Pz)``
  process grid (:func:`factor_pgrid` — auto from the operator's cell grid
  when the problem carries geometry via an ``A.grid`` attribute, a
  bandwidth-ordered 1-D chain after RCM otherwise), each device owns a
  box of cells, and only the referenced **faces/edges/corners** cross the
  wire: O((s/P^{1/3})²) values per face instead of the 1-D strip's O(s²).

The block partition's exchange is organized into **rounds**: one
``ppermute`` per round, where a round packs every (src → dst) neighbor
pair whose sources and destinations are disjoint (a greedy edge coloring
of the communication digraph).  At ``(2, 2, 2)`` the ±x face pairs share
no endpoints and merge into a single round, as do all four xy-edge
diagonals — 7 rounds total for a 27-point stencil (3 face, 3 edge, 1
corner) instead of 26 per-direction collectives.  This packing is what
makes 3-D win: per-round padding to the widest pair is paid once per
round, not once per direction.

The resulting :class:`BlockPartition` is **also a layout**: a permutation
of the padded index space that places each device's interior cells first
and its boundary cells in the last ``n_boundary`` slots of its chunk, so
the local SpMV can contract interior rows (no remote deps) while the face
``ppermute``s are in flight — the communication/compute overlap the
sharded driver's matvec exploits (:func:`repro.sparse.shard.partition_matvec`).
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "HaloProbe",
    "halo_probe",
    "BlockPartition",
    "block_partition",
    "candidate_pgrids",
    "factor_pgrid",
    "grid_of",
]

#: a halo this fraction of the (padded) vector or larger -> gather instead
MAX_HALO_FRAC = 0.5


@dataclasses.dataclass(frozen=True)
class HaloProbe:
    """Host-side bandwidth/halo geometry of one (operator, shard count).

    ``strips`` are the per-hop exchange strip lengths (hop 1 first): every
    strip but the last is a full chunk, and ``sum(strips) == bandwidth`` —
    the one-sided halo width.  ``mode`` is the partition mode the probe
    recommends: ``"halo"`` for banded operators whose two-sided halo stays
    under :data:`MAX_HALO_FRAC` of the padded vector, ``"rows"`` for
    wide/unstructured ELL-convertible operators, ``"replicated"`` when the
    operator cannot be row-partitioned at all.
    """

    n: int              # logical operator dim
    n_pad: int          # padded dim (multiple of n_shards)
    n_local: int        # chunk length per shard
    bandwidth: int      # max |col - row| over nonzeros (one-sided halo)
    hops: int           # neighbor distance needed on each side
    strips: tuple       # per-hop strip lengths, hop 1 first
    mode: str           # recommended partition mode


def _ell_arrays(A):
    """(cols, vals) of an ELL view of ``A``; None if not convertible."""
    if hasattr(A, "cols") and hasattr(A, "vals"):
        return A.cols, A.vals
    if hasattr(A, "to_ell"):
        E = A.to_ell()
        return E.cols, E.vals
    return None


def _bandwidth_of(A, ell=None) -> int:
    if hasattr(A, "bandwidth"):
        return A.bandwidth()
    cols, vals = _ell_arrays(A) if ell is None else ell
    live = np.asarray(vals) != 0
    rows = np.arange(np.asarray(cols).shape[0])[:, None]
    off = np.abs(np.asarray(cols) - rows)[live]
    return int(off.max()) if off.size else 0


def halo_probe(A, n_shards: int, *,
               max_halo_frac: float = MAX_HALO_FRAC) -> HaloProbe:
    """Probe ``A``'s column structure for neighbor-exchange viability.

    Pure host work (numpy over the CSR/ELL index arrays); the result is
    what :func:`partition_matvec` partitions by and what the wire-bytes
    accounting (``benchmarks/shard_wire.py``) prices.
    """
    n = A.shape[0]
    n_pad = -(-n // n_shards) * n_shards
    n_local = n_pad // n_shards
    if not (hasattr(A, "to_ell") or (hasattr(A, "cols")
                                     and hasattr(A, "vals"))):
        return HaloProbe(n=n, n_pad=n_pad, n_local=n_local, bandwidth=0,
                         hops=0, strips=(), mode="replicated")
    bw = _bandwidth_of(A)
    hops = -(-bw // n_local) if bw else 0
    strips = tuple(
        min(n_local, bw - (k - 1) * n_local) for k in range(1, hops + 1)
    )
    mode = "halo" if 2 * bw < max_halo_frac * n_pad else "rows"
    return HaloProbe(n=n, n_pad=n_pad, n_local=n_local, bandwidth=bw,
                     hops=hops, strips=strips, mode=mode)


# ---------------------------------------------------------------------------
# 3-D block partition
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class BlockPartition:
    """One operator's 3-D block layout + face-exchange schedule.

    ``operator`` is the operator rebuilt in **block layout**: the padded
    index space is permuted (``perm[new] = old``, pad rows ≥ n map to
    themselves) so device ``p`` owns rows ``[p * n_local, (p+1) *
    n_local)``, with its boundary rows (rows referencing any remote
    column) in the last ``n_boundary`` slots of the chunk and interior
    rows/padding before them.  All consumers (Jacobi diag, ELL arrays,
    contiguous chunk slicing) therefore work exactly as on the 1-D
    layout.

    The exchange schedule: ``rounds[k]`` is the tuple of ``(src, dst)``
    device pairs of round ``k`` (one ``ppermute`` each — sources and
    destinations within a round are disjoint), ``send_idx[k]`` the
    ``(P, wire_sizes[k])`` local indices each device gathers into its
    round-``k`` send buffer (rows of non-sources are zeros and never
    travel), and ``wire_sizes[k]`` the per-device values shipped — what
    :func:`repro.dist.collectives.exchange_bytes` prices.  ``lcols`` /
    ``vals`` are the ``(n_pad, w)`` ELL arrays with columns localized
    against ``[local chunk | round-0 recv | round-1 recv | ...]``;
    interior rows (the first ``n_local - n_boundary`` of each chunk)
    reference only local columns by construction.
    """

    n: int                      # logical operator dim
    n_pad: int                  # P * n_local
    n_local: int                # max box size over devices
    grid: tuple                 # (nx, ny, nz) cell grid used
    pgrid: tuple                # (Px, Py, Pz) process grid
    order: str                  # cell ordering: "grid" | "identity" | "rcm"
    n_boundary: int             # uniform boundary-row count per chunk tail
    rounds: tuple               # rounds[k] = ((src, dst), ...)
    wire_sizes: tuple           # wire_sizes[k] = values sent per src device
    perm: np.ndarray            # (n_pad,) new -> old over padded indices
    send_idx: tuple             # send_idx[k] = (P, wire_sizes[k]) int32
    lcols: np.ndarray           # (n_pad, w) int32 localized ELL columns
    vals: np.ndarray            # (n_pad, w) ELL values, block layout
    operator: object            # the operator permuted into block layout


def grid_of(A):
    """``(nx, ny, nz)`` cell geometry of ``A``, or ``None``.

    Problem generators that know their grid attach it as a plain
    ``A.grid`` attribute (:mod:`repro.sparse.problems`); anything whose
    product does not match the operator dim is ignored — a permuted or
    sliced operator has lost its lexicographic meaning (``permute_csr``
    and pytree round-trips drop the attribute entirely).
    """
    g = getattr(A, "grid", None)
    if g is None:
        return None
    try:
        g = tuple(int(d) for d in g)
    except (TypeError, ValueError):
        return None
    if len(g) != 3 or any(d < 1 for d in g):
        return None
    if g[0] * g[1] * g[2] != A.shape[0]:
        return None
    return g


def candidate_pgrids(n_shards: int, grid: tuple) -> list:
    """All ordered ``(Px, Py, Pz)`` factor triples of ``n_shards`` that fit
    ``grid`` (``Pd <= grid_d``), deterministic order.  Degenerate grids
    degrade gracefully: a 2-D grid ``(nx, ny, 1)`` forces ``Pz = 1`` and a
    1-D chain ``(n, 1, 1)`` recovers the contiguous row split."""
    P = int(n_shards)
    out = []
    for px in range(1, P + 1):
        if P % px:
            continue
        for py in range(1, P // px + 1):
            if (P // px) % py:
                continue
            pg = (px, py, P // px // py)
            if all(p <= g for p, g in zip(pg, grid)):
                out.append(pg)
    if not out:
        raise ValueError(
            f"cannot factor {P} shards over cell grid {grid}: no "
            f"(Px, Py, Pz) with Px*Py*Pz == {P} fits the grid dims")
    return out


def factor_pgrid(n_shards: int, grid: tuple, *, A=None, rank=None) -> tuple:
    """Best ``(Px, Py, Pz)`` factorization of ``n_shards`` over ``grid``.

    With an operator ``A`` (the path :func:`block_partition` takes), every
    candidate triple is scored by its **actual modelled wire**: the ghost
    columns each (src, dst) device pair references are counted in original
    coordinates (the set is layout-independent) and packed into exchange
    rounds exactly as the real schedule will be — so the choice optimizes
    the quantity the benchmark gate measures, not a surface-area proxy
    (which misses per-round maxima and merged edge/corner traffic; on a
    13³ stencil it would pick ``(1, 2, 4)`` over the truly-cheaper
    ``(2, 2, 2)``).  Without ``A``, falls back to minimizing total face
    surface.  Ties break toward the most cubic boxes, then
    lexicographically — deterministic across runs.
    """
    best = None
    if A is not None:
        er, ec = _live_entries(A)
        if rank is None:
            rank = np.arange(A.shape[0])
    for pg in candidate_pgrids(n_shards, grid):
        boxes = tuple(-(-g // p) for g, p in zip(grid, pg))
        if A is not None:
            owner = _owner_of(rank, grid, pg)
            wire = sum(_pack_sizes(_pair_ghost_counts(er, ec, owner,
                                                      int(n_shards))))
        else:
            wire = 0
            for d in range(3):
                if pg[d] > 1:
                    area = 1
                    for e in range(3):
                        if e != d:
                            area *= boxes[e]
                    wire += 2 * area
        score = (wire, max(boxes), pg)
        if best is None or score < best:
            best = score
    return best[2]


def _validate_pgrid(pgrid, n_shards: int, grid: tuple) -> tuple:
    pg = tuple(int(p) for p in pgrid)
    if len(pg) != 3 or any(p < 1 for p in pg):
        raise ValueError(f"process grid must be 3 positive ints, got {pgrid}")
    if pg[0] * pg[1] * pg[2] != n_shards:
        raise ValueError(
            f"process grid {pg} has {pg[0] * pg[1] * pg[2]} cells but the "
            f"operator is partitioned over {n_shards} shards")
    if any(p > g for p, g in zip(pg, grid)):
        raise ValueError(
            f"process grid {pg} exceeds the cell grid {grid} in some dim")
    return pg


def _live_entries(A):
    """``(rows, cols)`` of the live (value != 0) entries, host numpy."""
    if hasattr(A, "indptr") and hasattr(A, "indices"):
        indptr = np.asarray(A.indptr)
        rows = np.repeat(np.arange(A.shape[0]), np.diff(indptr))
        cols = np.asarray(A.indices)
        live = np.asarray(A.data) != 0
        return rows[live], cols[live]
    cols, vals = _ell_arrays(A)
    cols, vals = np.asarray(cols), np.asarray(vals)
    live = vals != 0
    rows = np.broadcast_to(np.arange(cols.shape[0])[:, None], cols.shape)
    return rows[live], cols[live]


def _axis_bounds(dim: int, parts: int) -> np.ndarray:
    """Start offsets of a near-even split of ``dim`` cells into ``parts``."""
    sizes = np.full(parts, dim // parts)
    sizes[: dim % parts] += 1
    return np.concatenate([[0], np.cumsum(sizes)])


def _owner_of(rank, grid, pgrid) -> np.ndarray:
    """Device owning each row: cell coords from chain rank, boxes from a
    near-even axis split, device = (bx * Py + by) * Pz + bz."""
    nx, ny, nz = grid
    px, py, pz = pgrid
    cz = rank % nz
    cy = (rank // nz) % ny
    cx = rank // (ny * nz)
    bx = np.searchsorted(_axis_bounds(nx, px), cx, side="right") - 1
    by = np.searchsorted(_axis_bounds(ny, py), cy, side="right") - 1
    bz = np.searchsorted(_axis_bounds(nz, pz), cz, side="right") - 1
    return ((bx * py + by) * pz + bz).astype(np.int64)


def _pair_ghost_counts(er, ec, owner, P: int) -> dict:
    """{(src, dst): ghost column count} over the live entries — the number
    of distinct remote values each device pair actually references.  The
    count is layout-independent, so candidate process grids can be scored
    before any layout is built."""
    g = owner[er] != owner[ec]
    if not g.any():
        return {}
    key = owner[ec[g]] * P + owner[er[g]]
    uniq = np.unique(np.stack([key, ec[g]]), axis=1)
    ks, counts = np.unique(uniq[0], return_counts=True)
    return {(int(k) // P, int(k) % P): int(c)
            for k, c in zip(ks, counts)}


def _pack_rounds(pairs):
    """Greedy edge coloring: pack (src, dst, ghost) pairs into rounds
    whose sources and destinations are disjoint, widest pairs first."""
    pairs = sorted(pairs, key=lambda t: (-_size_of(t[2]), t[0], t[1]))
    rounds = []
    for src, dst, gc in pairs:
        for rd in rounds:
            if src not in rd["srcs"] and dst not in rd["dsts"]:
                rd["srcs"].add(src)
                rd["dsts"].add(dst)
                rd["items"].append((src, dst, gc))
                break
        else:
            rounds.append(dict(srcs={src}, dsts={dst},
                               items=[(src, dst, gc)]))
    return rounds


def _size_of(gc) -> int:
    return gc if isinstance(gc, int) else gc.size


def _pack_sizes(pair_counts: dict) -> list:
    """Per-round wire sizes (max pair width per round) of the greedy
    packing — the modelled wire a candidate process grid would move."""
    packed = _pack_rounds(
        [(s, d, c) for (s, d), c in pair_counts.items()])
    return [max(_size_of(gc) for _, _, gc in rd["items"]) for rd in packed]


def block_partition(A, n_shards: int, *, pgrid=None) -> BlockPartition:
    """Build the 3-D block layout + face-exchange schedule for ``A``.

    When ``A`` carries cell geometry (:func:`grid_of`) the cells are its
    lexicographic grid points; otherwise the cells form a 1-D chain in
    RCM order (identity order when the operator is already banded) — the
    unstructured fallback, which still ships only the *actually
    referenced* ghost values instead of full bandwidth strips.  ``pgrid``
    forces the process-grid factorization (default: :func:`factor_pgrid`).
    """
    P = int(n_shards)
    if P < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    n = A.shape[0]
    ell = _ell_arrays(A)
    if ell is None:
        raise ValueError(
            f"mode='block3d' needs an ELL-convertible operator "
            f"(got {type(A).__name__}); use mode='replicated'")

    grid = grid_of(A)
    rank = np.arange(n)             # cell order: rank[row] = chain position
    order_kind = "grid"
    if grid is None:
        bw = _bandwidth_of(A, ell)
        if 2 * bw >= MAX_HALO_FRAC * n:
            from repro.sparse.reorder import rcm_permutation

            seq = rcm_permutation(A)            # seq[pos] = row
            rank = np.empty(n, np.int64)
            rank[seq] = np.arange(n)
            order_kind = "rcm"
        else:
            order_kind = "identity"
        grid = (n, 1, 1)
    pgrid = (factor_pgrid(P, grid, A=A, rank=rank) if pgrid is None
             else _validate_pgrid(pgrid, P, grid))

    # -- owner map: cell coords -> device --------------------------------
    r = np.arange(n)
    owner = _owner_of(rank, grid, pgrid)

    # -- boundary rows: any live column owned elsewhere ------------------
    er, ec = _live_entries(A)
    is_boundary = np.zeros(n, bool)
    is_boundary[er[owner[er] != owner[ec]]] = True

    # -- layout: per device [interior | pads | boundary], boundary rows in
    #    the last n_boundary slots of every chunk (uniform, so the local
    #    matvec's interior/boundary row split is one static slice) --------
    box_sizes = np.bincount(owner, minlength=P)
    n_local = int(box_sizes.max()) if P else 0
    n_pad = P * n_local
    nb = 0
    chunks = []
    next_pad = n
    for p in range(P):
        box = r[owner == p]
        box = box[np.argsort(rank[box], kind="stable")]
        bnd = box[is_boundary[box]]
        nb = max(nb, bnd.size)
    for p in range(P):
        box = r[owner == p]
        box = box[np.argsort(rank[box], kind="stable")]
        bnd = box[is_boundary[box]]
        interior = box[~is_boundary[box]]
        n_fill = n_local - box.size
        pads = np.arange(next_pad, next_pad + n_fill)
        next_pad += n_fill
        chunks.append(np.concatenate([interior, pads, bnd]))
    perm = (np.concatenate(chunks).astype(np.int64) if P
            else np.arange(0, dtype=np.int64))

    # -- operator in block layout (pad empty rows, then permute) ---------
    from repro.sparse.reorder import _csr_arrays, permute_csr
    from repro.sparse.csr import CSR

    indptr, indices, data = _csr_arrays(A)
    indptr = np.asarray(indptr)
    if n_pad > n:
        indptr = np.concatenate(
            [indptr, np.full(n_pad - n, indptr[-1], indptr.dtype)])
    op_blk = permute_csr(CSR(indptr, indices, data, (n_pad, n_pad)), perm)

    # -- ghost analysis in block coordinates -----------------------------
    br, bc = _live_entries(op_blk)
    rdev = br // n_local
    cdev = bc // n_local
    ghost = rdev != cdev
    pair_cols = {}
    if ghost.any():
        key = cdev[ghost] * P + rdev[ghost]
        uniq = np.unique(np.stack([key, bc[ghost]]), axis=1)
        for k in np.unique(uniq[0]):
            pair_cols[(int(k) // P, int(k) % P)] = uniq[1][uniq[0] == k]
    pairs = [(src, dst, gc) for (src, dst), gc in pair_cols.items()]
    packed = _pack_rounds(pairs)

    rounds, wire_sizes, send_idx = [], [], []
    for rd in packed:
        L = max(gc.size for _, _, gc in rd["items"])
        idx = np.zeros((P, L), np.int32)
        prs = []
        for src, dst, gc in sorted(rd["items"]):
            idx[src, : gc.size] = gc - src * n_local
            prs.append((src, dst))
        rounds.append(tuple(prs))
        wire_sizes.append(L)
        send_idx.append(idx)

    # -- localized ELL columns against [chunk | recv_0 | recv_1 | ...] ---
    E_cols, E_vals = _ell_arrays(op_blk)
    cols_e, vals_e = np.asarray(E_cols), np.asarray(E_vals)
    live = vals_e != 0
    rdev_e = (np.arange(n_pad) // n_local)[:, None] if n_pad else \
        np.zeros((0, 1), np.int64)
    cdev_e = cols_e // n_local if n_local else cols_e
    lcols = np.where(live & (cdev_e == rdev_e),
                     cols_e - rdev_e * n_local, 0).astype(np.int64)
    offs = n_local + np.concatenate([[0], np.cumsum(wire_sizes)])
    for k, rd in enumerate(packed):
        for src, dst, gc in rd["items"]:
            m = live & (cdev_e == src) & (rdev_e == dst)
            if m.any():
                lcols[m] = offs[k] + np.searchsorted(gc, cols_e[m])

    # interior rows (first n_local - nb slots of each chunk) must be fully
    # local — the overlap split's correctness invariant
    ghost_rows = br[ghost]
    if ghost_rows.size and int((ghost_rows % n_local).min()) < n_local - nb:
        raise AssertionError("block partition: ghost entry in an interior "
                             "row — layout invariant violated")

    return BlockPartition(
        n=n, n_pad=n_pad, n_local=n_local, grid=grid, pgrid=pgrid,
        order=order_kind, n_boundary=nb, rounds=tuple(rounds),
        wire_sizes=tuple(wire_sizes), perm=perm, send_idx=tuple(send_idx),
        lcols=lcols.astype(np.int32), vals=vals_e, operator=op_blk)
