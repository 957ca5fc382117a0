"""FRSZ2-compressed cross-pod collectives (the paper's codec on the wire).

Multi-pod data parallelism all-reduces gradients over a slow inter-pod
fabric; that transfer is exactly as bandwidth-bound as the paper's Krylov
basis reads, so the same trick applies: ship FRSZ2 *codes* (uint16 for
frsz2_16 — half the f32 wire bytes, plus a 1/128 exponent stream) and
decompress after the gather.

``compressed_pmean(tree, axis_name)`` runs inside ``shard_map``/``pmap``:
each leaf is block-compressed locally, the codes+exponents are
``all_gather``ed over ``axis_name`` (the HLO genuinely carries u16 — tests
assert it), and the mean is taken over the decompressed shards.  The mean
is exact up to codec error (≤ 2^-14 of the per-block max for frsz2_16);
convergence-relevant bias is zero because truncation is applied before the
sum of independently-signed shards.

``pmean_bytes`` accounts wire bytes per device for the plain vs compressed
variant (used by the roofline analysis and the multi-device test).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import frsz2 as F

__all__ = [
    "WIRE_SPEC",
    "compressed_pmean",
    "compressed_psum",
    "exchange_bytes",
    "gather_bytes",
    "gather_operand",
    "halo_bytes",
    "halo_exchange",
    "halo_exchange_3d",
    "halo_wire_spec",
    "perm_defect",
    "pmean_bytes",
    "psum",
    "reduce_bytes",
    "rounds_defect",
]

#: wire codec: frsz2_16 over 128-value blocks (2 B codes + 4 B/128 exps)
WIRE_SPEC = F.FrszSpec(bs=128, l=16, dtype=jnp.float32)


def halo_wire_spec(dtype) -> F.FrszSpec:
    """Wire codec for halo strips: frsz2 at *half* the operand width.

    Halo values feed the operator (they are multiplied by matrix entries),
    so they ride a higher-fidelity codec than the dots' partial-sum stream:
    frsz2_32 for f64 operands (the paper's flagship format — ~2^-30 of the
    block max, half the f64 wire bytes), frsz2_16 for f32.
    """
    if jnp.dtype(dtype) == jnp.dtype("float64"):
        return F.FrszSpec(bs=128, l=32, dtype=jnp.float64)
    return WIRE_SPEC


def psum(x, axis_name: str):
    """Plain psum through the audited wire layer.

    The one blessed spelling outside this module (the jaxlint
    ``raw-collective`` rule rejects direct ``lax.psum`` elsewhere): a
    reduction routed here is priced by :func:`reduce_bytes` with
    ``compressed=False``, so the wire accounting the benchmarks gate on
    stays complete by construction.  Named scope ``allreduce``, as every
    reduction of this module.
    """
    with jax.named_scope("allreduce"):
        return jax.lax.psum(x, axis_name)


def gather_operand(x_local, axis_name: str):
    """Tiled all_gather of a row-partitioned operand chunk.

    Reassembles the full vector from per-device ``(n_local,)`` chunks —
    the transport behind the ``"rows"``/``"replicated"`` SpMV partitions.
    Priced by :func:`gather_bytes`; like :func:`psum` it exists so every
    fabric-crossing byte moves through this module.
    """
    return jax.lax.all_gather(x_local, axis_name, tiled=True)


def _compress_leaf(x):
    """Flatten + FRSZ2-compress one gradient leaf (f32 wire dtype)."""
    return F.compress(x.reshape(-1).astype(jnp.float32), WIRE_SPEC)


def _gathered_shards(x, axis_name: str):
    """All-gather one leaf's FRSZ2 codes over ``axis_name``; returns the
    decompressed ``(P, n_flat)`` per-device shards."""
    bc = _compress_leaf(x)
    codes = jax.lax.all_gather(bc.codes, axis_name)       # (P, nb, bs) u16
    exps = jax.lax.all_gather(bc.exps, axis_name)         # (P, nb)
    gathered = F.BlockCompressed(
        codes=codes, exps=exps, n=bc.n, spec=WIRE_SPEC
    )
    return F.decompress(gathered)                         # (P, n_flat)


def compressed_pmean(tree, axis_name: str):
    """Mean of ``tree`` over ``axis_name`` with FRSZ2-compressed transport."""

    def leaf_pmean(x):
        mean = jnp.mean(_gathered_shards(x, axis_name), axis=0)
        return mean[: x.size].reshape(x.shape).astype(x.dtype)

    with jax.named_scope("allreduce"):
        return jax.tree.map(leaf_pmean, tree)


def compressed_psum(tree, axis_name: str):
    """Sum of ``tree`` over ``axis_name`` with FRSZ2-compressed transport.

    The transport for partial reductions whose *operands* live sharded —
    e.g. the per-device partial dot products of a sharded Krylov basis
    (``sharded:<fmt>`` storage): each device ships its contribution as
    frsz2_16 codes and sums the decompressed gather.
    """

    def leaf_psum(x):
        total = jnp.sum(_gathered_shards(x, axis_name), axis=0)
        return total[: x.size].reshape(x.shape).astype(x.dtype)

    with jax.named_scope("allreduce"):
        return jax.tree.map(leaf_psum, tree)


# ---------------------------------------------------------------------------
# Permutation/round structure (shared by the exchanges, spmdcheck, and the
# property tests — one definition of "well-formed" for every ppermute we issue)
# ---------------------------------------------------------------------------


def perm_defect(perm, axis_size: int | None = None) -> str | None:
    """Why ``perm`` is not a partial injection on ``[0, axis_size)``.

    A ``ppermute`` permutation is well-formed iff every source appears at
    most once (a device cannot send two payloads in one collective) and
    every destination appears at most once (two senders to one receiver
    deadlock or clobber); unaddressed devices are fine — they send nothing
    and receive zeros.  Returns ``None`` when well-formed, else a short
    human-readable reason naming the offending index.
    """
    seen_src: set[int] = set()
    seen_dst: set[int] = set()
    for pair in perm:
        try:
            src, dst = (int(pair[0]), int(pair[1]))
        except (TypeError, ValueError, IndexError):
            return f"pair {pair!r} is not an (src, dst) index pair"
        if axis_size is not None and not (
                0 <= src < axis_size and 0 <= dst < axis_size):
            return (f"pair ({src}, {dst}) outside the axis range "
                    f"[0, {axis_size})")
        if src in seen_src:
            return f"source {src} appears twice"
        if dst in seen_dst:
            return f"destination {dst} appears twice"
        seen_src.add(src)
        seen_dst.add(dst)
    return None


def rounds_defect(rounds, axis_size: int | None = None) -> str | None:
    """Why a round schedule is not a pairwise-disjoint partial-injection set.

    ``rounds`` is a sequence of ppermute permutations (the 3-D halo's
    exchange schedule): each round must be a partial injection
    (:func:`perm_defect`) and no directed ``(src, dst)`` channel may appear
    in two rounds — a repeated channel double-ships the same link and the
    receive buffers would alias.  Returns ``None`` when well-formed.
    """
    seen_pairs: set[tuple[int, int]] = set()
    for k, perm in enumerate(rounds):
        defect = perm_defect(perm, axis_size)
        if defect is not None:
            return f"round {k}: {defect}"
        for src, dst in perm:
            channel = (int(src), int(dst))
            if channel in seen_pairs:
                return (f"round {k}: channel {channel} already used by an "
                        "earlier round")
            seen_pairs.add(channel)
    return None


# ---------------------------------------------------------------------------
# Neighbor halo exchange (banded SpMV: boundary strips instead of all_gather)
# ---------------------------------------------------------------------------


def _ppermute(x, axis_name: str, perm, compressed: bool):
    """``ppermute`` with optional FRSZ2-compressed transport.

    ``ppermute`` fills unaddressed destinations with zeros, which is exactly
    the open (non-periodic) boundary a banded operator needs — no column of
    a real matrix row reaches outside [0, n).  With ``compressed`` the
    payload travels as FRSZ2 codes (:func:`halo_wire_spec`): zero codes
    decompress to exact zeros, so the edge semantics survive compression.
    """
    if not compressed:
        return jax.lax.ppermute(x, axis_name, perm)
    spec = halo_wire_spec(x.dtype)
    bc = F.compress(x, spec)
    codes = jax.lax.ppermute(bc.codes, axis_name, perm)
    exps = jax.lax.ppermute(bc.exps, axis_name, perm)
    moved = F.BlockCompressed(codes=codes, exps=exps, n=bc.n, spec=spec)
    return F.decompress(moved).astype(x.dtype)


def _pshift(x, k: int, n_shards: int, axis_name: str, compressed: bool):
    """Receive the neighbor-at-distance-``k``'s copy of ``x`` (0 < |k| <
    n_shards): device ``p`` gets device ``p - k``'s value, edges get zeros.
    """
    perm = [(i, i + k) for i in range(n_shards) if 0 <= i + k < n_shards]
    return _ppermute(x, axis_name, perm, compressed)


def halo_exchange(x_local, strips, n_shards: int, axis_name: str, *,
                  compressed: bool = False):
    """Extend this device's chunk with neighbor boundary strips.

    ``x_local`` is the ``(n_local,)`` chunk of a row-partitioned vector;
    ``strips`` the per-hop strip lengths from the halo probe (hop 1 first;
    every strip but the last is a full chunk).  Returns the ``(n_local +
    2 * halo,)`` extended vector ``[left halo | x_local | right halo]``
    with ``halo = sum(strips)`` — the operand a banded local SpMV contracts
    against.  Only ``2 * halo`` values cross the wire per device instead of
    the ``(n_shards - 1) * n_local`` a tiled ``all_gather`` moves
    (:func:`halo_bytes` vs :func:`gather_bytes`).

    Runs inside ``shard_map`` with ``axis_name`` bound.  ``compressed``
    ships the strips as FRSZ2 codes (:func:`halo_wire_spec` — half the
    operand width).  Named scope ``halo``.
    """
    n_local = x_local.shape[0]
    left, right = [], []
    with jax.named_scope("halo"):
        for k, s in enumerate(strips, start=1):
            if not 0 < s <= n_local:
                raise ValueError(
                    f"strip {k} of {strips} not in (0, {n_local}]")
            # left halo: the trailing s values of the k-hop left neighbor
            left.append(_pshift(x_local[n_local - s:], +k, n_shards,
                                axis_name, compressed))
            # right halo: the leading s values of the k-hop right neighbor
            right.append(_pshift(x_local[:s], -k, n_shards, axis_name,
                                 compressed))
        # farthest-first on the left, nearest-first on the right: global
        # order
        return jnp.concatenate(left[::-1] + [x_local] + right)


def halo_exchange_3d(x_local, send_idx, rounds, axis_name: str, *,
                     compressed: bool = False):
    """Extend this device's chunk with neighbor face/edge/corner values.

    The 3-D counterpart of :func:`halo_exchange`: instead of contiguous
    bandwidth strips, each exchange *round* gathers the referenced ghost
    values (``x_local[send_idx[k]]``, a precomputed per-round index map
    from :func:`repro.sparse.halo_probe.block_partition`) and ships them in
    one ``ppermute`` along the round's disjoint ``(src, dst)`` pairs —
    devices not sourcing a pair in that round send to nobody and receive
    zeros, which never get referenced (the localized ELL columns only point
    into buffers the row's operator entries actually populate).

    Returns ``[x_local | recv_0 | recv_1 | ...]``, the operand the
    block-layout local SpMV contracts boundary rows against.  ``compressed``
    ships each round's buffer as FRSZ2 codes (:func:`halo_wire_spec`).
    Runs inside ``shard_map`` with ``axis_name`` bound; under ``jax.vmap``
    the gathers/ppermutes batch, so one exchange serves a whole RHS block.
    Named scope ``halo``.
    """
    defect = rounds_defect(rounds)
    if defect is not None:
        raise ValueError(f"malformed exchange rounds: {defect}")
    with jax.named_scope("halo"):
        bufs = [
            _ppermute(x_local[..., idx], axis_name, list(pairs), compressed)
            for idx, pairs in zip(send_idx, rounds)
        ]
        if not bufs:
            return x_local
        return jnp.concatenate([x_local, *bufs], axis=-1)


def exchange_bytes(sizes, *, compressed: bool = False,
                   plain_itemsize: int = 8, dtype=jnp.float64) -> int:
    """Per-device wire payload of one exchange shipping ``sizes`` buffers.

    The single audited pricing path for every neighbor-exchange flavor:
    ``sizes`` is the per-``ppermute`` operand length, i.e. the values one
    device *sends* in each collective — per-hop strips twice (once per
    direction) for the 1-D halo, per-round buffer lengths for the 3-D face
    exchange.  Compressed buffers ride :func:`halo_wire_spec` for ``dtype``
    and pay FRSZ2's whole-block granularity per buffer (a 1-value corner
    still ships a 128-code block).
    """
    if compressed:
        spec = halo_wire_spec(dtype)
        return sum(F.storage_nbytes(int(s), spec) for s in sizes)
    return int(sum(int(s) for s in sizes)) * plain_itemsize


def halo_bytes(strips, *, compressed: bool = False, plain_itemsize: int = 8,
               dtype=jnp.float64) -> int:
    """Per-device wire payload of one :func:`halo_exchange`.

    Each strip is both sent and received on each side, so a device moves
    ``2 * sum(strips)`` values — priced through :func:`exchange_bytes` as
    two sends per strip.
    """
    return exchange_bytes(tuple(strips) * 2, compressed=compressed,
                          plain_itemsize=plain_itemsize, dtype=dtype)


def gather_bytes(n_local: int, n_shards: int, *,
                 plain_itemsize: int = 8) -> int:
    """Per-device wire payload of one tiled ``all_gather``.

    A ring all-gather forwards every other device's chunk through each
    link: each device transmits (and receives) ``n_shards - 1`` chunks, not
    just its own — the quantity the halo exchange is competing against.
    """
    return (n_shards - 1) * n_local * plain_itemsize


def reduce_bytes(n_values: int, *, compressed: bool,
                 plain_itemsize: int = 8) -> int:
    """Per-device wire payload for one psum of ``n_values`` values.

    The quantity the sharded-GMRES wire accounting sums per collective:
    with plain transport each device ships its partial sums at the
    arithmetic width (f64 by default); with compressed transport it ships
    FRSZ2 codes + the per-block exponent stream (``WIRE_SPEC``).  Note the
    block granularity: a payload below one 128-value block still pays for a
    whole block, which is why compressing *scalar* norm reductions costs
    more wire than plain psum (``benchmarks/shard_wire.py`` tabulates it).
    """
    if compressed:
        return F.storage_nbytes(n_values, WIRE_SPEC)
    return n_values * plain_itemsize


def pmean_bytes(tree, *, compressed: bool) -> int:
    """Wire bytes per device for one pmean of ``tree``.

    The plain path ships each leaf at its own itemsize (an f64 gradient
    leaf costs 8 B/value, not the f32 4 B this helper once assumed); the
    compressed path is the actual code + exponent stream of ``WIRE_SPEC``
    (independent of the leaf dtype — the codec casts to its wire dtype).
    """
    total = 0
    for leaf in jax.tree.leaves(tree):
        n = int(np.prod(leaf.shape)) if leaf.ndim else 1
        if compressed:
            total += F.storage_nbytes(n, WIRE_SPEC)
        else:
            total += n * jnp.dtype(leaf.dtype).itemsize
    return total
