"""Row-partitioned SpMV for the sharded GMRES driver (inside shard_map).

The sharded solver keeps every vector row-partitioned over the mesh axis:
each device owns an ``(n_local,)`` chunk.  The Arnoldi matvec therefore
needs ``y_local = (A x)_local`` from ``x_local``.  Three applications are
provided, selected by :func:`partition_matvec`:

* ``"halo"`` (default for banded CSR/ELL) — **row-partitioned,
  neighbor-exchange halo**: a host-side probe (:func:`halo_probe`) measures
  the column bandwidth of the operator and precomputes per-shard halo index
  maps; at solve time each device ``ppermute``s only its boundary strips to
  the left/right neighbors (multi-hop when the bandwidth spans several
  chunks, :func:`repro.dist.collectives.halo_exchange`) and contracts its
  rows against ``[left halo | local chunk | right halo]``.  Wire cost per
  matvec: ``O(bandwidth)`` values instead of the ``O(n)`` a gathered
  operand moves (:func:`~repro.dist.collectives.halo_bytes` vs
  :func:`~repro.dist.collectives.gather_bytes`).

* ``"rows"`` — **row-partitioned, gathered-halo**: the operator is
  converted to ELL and its ``(n, w)`` ``cols``/``vals`` arrays enter
  ``shard_map`` partitioned along dim 0; the operand vector is
  ``all_gather``ed to full length, then the local rows contract against
  it.  The always-correct fallback for unstructured sparsity — and what
  ``"halo"`` falls back to when the probe finds the halo would be ≥ ~half
  the vector anyway.  Per-device operator memory: ``1/P`` of the matrix.

* ``"replicated"`` — **replicated-operand**: the operator enters
  ``shard_map`` fully replicated (spec ``P()`` on every leaf), each device
  computes the full ``A x`` and keeps its own row slice.  No conversion,
  works for any pytree operator with ``.matvec``; costs full-matrix memory
  and flops per device, so it is the fallback, not the default.

* ``"block3d"`` — **3-D block partition, face exchange, overlapped**: the
  plan's :class:`~repro.sparse.halo_probe.BlockPartition` assigns each
  device a 3-D box of grid cells (2-D/1-D degenerate cases included), so
  only the referenced faces/edges/corners travel —
  O((s/P^{1/3})²) values per face on an s³ grid instead of the 1-D
  strip's O(s²).  The local contraction is *split*: the face
  ``ppermute``s (:func:`repro.dist.collectives.halo_exchange_3d`) are
  issued first, then the interior rows (no remote deps, the first
  ``n_local - n_boundary`` of the chunk) contract against the local chunk
  alone, and only the boundary rows touch the exchange result — XLA's
  latency-hiding scheduler can overlap the collective with the interior
  work.

Operator dims that do not divide the shard count are zero-padded up to the
next multiple (padded rows carry val 0, padded operand entries are zeros,
so the padded SpMV embeds the original exactly); callers pad their vectors
to ``probe.n_pad`` and trim the result.

All modes return the same triple, ready to splice into a ``shard_map``
call::

    operand, in_specs, local_mv = partition_matvec(A, n_shards=P)
    # shard_map(f, in_specs=(in_specs, ...)); inside f:
    y_local = local_mv(operand_local, x_local)

The returned ``local_mv`` carries ``.mode`` (the executed path), ``.probe``
(the :class:`HaloProbe`), ``.plan`` (the
:class:`~repro.sparse.plan.OperatorPlan` the partition was built from —
wire accounting and tests read it), and ``.exact`` — the same partition
with lossless transport (identical to ``local_mv`` unless a compressed
halo was requested), which the driver's explicit residual recomputations
use.

Host-side preparation (bandwidth probing, mode arbitration, optional RCM
reordering, zero-padding, ELL conversion) is owned by
:mod:`repro.sparse.plan`; this module keeps only the shard_map glue and
the local contraction kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import (
    gather_operand,
    halo_exchange,
    halo_exchange_3d,
)
from repro.sparse.csr import operator_matvec

# probing/partition geometry grew into its own module; the canonical home
# is repro.sparse.halo_probe — re-exported here for existing importers
from repro.sparse.halo_probe import (  # noqa: F401
    MAX_HALO_FRAC,
    BlockPartition,
    HaloProbe,
    _bandwidth_of,
    _ell_arrays,
    block_partition,
    halo_probe,
)

__all__ = ["BlockPartition", "HaloProbe", "block_partition", "halo_probe",
           "partition_matvec"]


def _validate_mesh(mesh, axis_name: str, n_shards: int):
    """Fail fast with a readable error instead of an opaque XLA one."""
    if mesh is None:
        return
    if axis_name not in mesh.axis_names:
        raise ValueError(
            f"partition axis {axis_name!r} is not on the mesh "
            f"(axes: {tuple(mesh.axis_names)}); the local matvec's "
            f"collectives would fail inside shard_map")
    if mesh.shape[axis_name] != n_shards:
        raise ValueError(
            f"mesh axis {axis_name!r} has size {mesh.shape[axis_name]} "
            f"but the operator is partitioned over {n_shards} shards")


def partition_matvec(A=None, n_shards: int | None = None,
                     axis_name: str = "basis", mode: str = "auto", *,
                     mesh=None, compressed_halo: bool = False, plan=None):
    """Split an operator for row-parallel SpMV under ``shard_map``.

    Returns ``(operand, in_specs, local_matvec)`` where ``operand`` is the
    pytree of arrays to pass into ``shard_map``, ``in_specs`` the matching
    PartitionSpec tree, and ``local_matvec(operand_local, x_local)`` maps
    this device's ``(n_local,)`` chunk of ``x`` to its chunk of ``A x``.

    The host-side prep — probing, mode arbitration, padding, ELL
    conversion — lives in an :class:`~repro.sparse.plan.OperatorPlan`.
    Pass one as ``plan=`` (the sharded driver does: the plan may have
    RCM-reordered the operator, and its prepared arrays are memoized);
    or pass ``(A, n_shards, mode)`` and a reorder-free plan is built
    here, preserving the original call shape.

    ``mode``: ``"auto"`` follows the probe (halo for banded operators,
    gathered rows for wide/unstructured ones, replicated for bare
    matvec-only operators — and the 3-D block partition when the operator
    carries cell geometry and its modelled face wire wins);
    ``"halo"``/``"rows"``/``"replicated"``/``"block3d"`` force a path —
    except that ``"halo"`` still falls back to the gathered-operand
    contraction when the probe finds the two-sided halo would be ≥
    ``MAX_HALO_FRAC`` of the vector (the exchange would move more than the
    gather).  The executed path is reported on ``local_matvec.mode``.
    ``"block3d"`` requires the plan's block layout: vectors must enter
    through :meth:`OperatorPlan.embed` (the layout interleaves pad slots
    inside chunks), and the contraction overlaps the face exchange with
    the interior rows.

    When the operator dim does not divide ``n_shards`` the operator rows
    are zero-padded to ``probe.n_pad``; pad the operand vectors to match
    and trim the padded tail of the result (padded rows produce exact
    zeros).

    ``mesh`` (optional) validates ``axis_name`` against the mesh the caller
    will run shard_map on; ``compressed_halo`` ships halo strips as FRSZ2
    codes (:func:`repro.dist.collectives.halo_exchange`).
    """
    if plan is None:
        from repro.sparse.plan import plan_operator

        if A is None or n_shards is None:
            raise ValueError(
                "partition_matvec needs either plan= or (A, n_shards)")
        plan = plan_operator(A, n_shards, reorder="none", matvec_mode=mode)
    elif n_shards is not None and n_shards != plan.n_shards:
        raise ValueError(
            f"n_shards={n_shards} conflicts with the plan's "
            f"{plan.n_shards}; pass one or the other")
    elif mode != "auto" and mode != plan.requested_matvec:
        raise ValueError(
            f"mode={mode!r} conflicts with the plan's requested "
            f"{plan.requested_matvec!r}; build the plan with this mode")
    A = plan.operator
    n_shards = plan.n_shards
    _validate_mesh(mesh, axis_name, n_shards)

    probe = plan.probe
    n_pad, n_local = plan.n_pad, plan.n_local
    mode = plan.matvec_mode
    n = plan.n

    exact_matvec = None
    if mode == "halo":
        lcols, vals = plan.ell_halo_localized()
        operand = (jnp.asarray(lcols, jnp.int32), jnp.asarray(vals))
        in_specs = (P(axis_name, None), P(axis_name, None))
        strips = probe.strips

        def _halo_matvec(op, x_local, compressed):
            lcols_l, vals_l = op                      # (n_local, w) each
            x_ext = halo_exchange(x_local, strips, n_shards, axis_name,
                                  compressed=compressed)
            return (vals_l * x_ext[lcols_l].astype(vals_l.dtype)).sum(axis=1)

        def local_matvec(op, x_local):
            return _halo_matvec(op, x_local, compressed_halo)

        if compressed_halo:
            def exact_matvec(op, x_local):
                return _halo_matvec(op, x_local, False)

    elif mode == "block3d":
        blk = plan.block
        operand = (jnp.asarray(blk.lcols, jnp.int32),
                   jnp.asarray(blk.vals),
                   tuple(jnp.asarray(ix, jnp.int32) for ix in blk.send_idx))
        in_specs = (P(axis_name, None), P(axis_name, None),
                    tuple(P(axis_name, None) for _ in blk.send_idx))
        rounds = blk.rounds
        ni = n_local - blk.n_boundary

        def _block3d_matvec(op, x_local, compressed):
            lcols_l, vals_l, send = op
            # issue the face ppermutes first, then contract the interior
            # rows (purely local by layout) so XLA can overlap them with
            # the in-flight exchange; only boundary rows read x_ext
            x_ext = halo_exchange_3d(x_local, tuple(ix[0] for ix in send),
                                     rounds, axis_name,
                                     compressed=compressed)
            y_int = (vals_l[:ni]
                     * x_local[lcols_l[:ni]].astype(vals_l.dtype)).sum(axis=1)
            y_bnd = (vals_l[ni:]
                     * x_ext[lcols_l[ni:]].astype(vals_l.dtype)).sum(axis=1)
            return jnp.concatenate([y_int, y_bnd])

        def local_matvec(op, x_local):
            return _block3d_matvec(op, x_local, compressed_halo)

        if compressed_halo:
            def exact_matvec(op, x_local):
                return _block3d_matvec(op, x_local, False)

    elif mode == "rows":
        cols, vals = plan.ell_padded()
        operand = (jnp.asarray(cols, jnp.int32), jnp.asarray(vals))
        in_specs = (P(axis_name, None), P(axis_name, None))

        def local_matvec(op, x_local):
            cols_l, vals_l = op                       # (n_local, w) each
            x = gather_operand(x_local, axis_name)
            return (vals_l * x[cols_l].astype(vals_l.dtype)).sum(axis=1)

    else:  # replicated
        operand = operator_matvec(A)
        in_specs = jax.tree.map(lambda _: P(), operand)
        pad = n_pad - n

        def local_matvec(mv, x_local):
            x = gather_operand(x_local, axis_name)
            y = mv(x[:n])
            if pad:
                y = jnp.pad(y, (0, pad))
            i = jax.lax.axis_index(axis_name)
            return jax.lax.dynamic_slice_in_dim(y, i * n_local, n_local)

    local_matvec.mode = mode
    local_matvec.probe = probe
    local_matvec.plan = plan
    # .exact applies the same partition with lossless transport (== the
    # matvec itself unless a compressed halo was requested): the driver's
    # explicit residual recomputations ride this one.
    local_matvec.exact = exact_matvec or local_matvec
    return operand, in_specs, local_matvec
