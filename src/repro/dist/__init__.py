"""Distribution layer: logical-axis sharding rules + compressed collectives.

Three small modules:

  act_sharding — scoped activation-sharding constraints: model code calls
                 ``constrain(x, "batch", None, "heads", None)`` with
                 *logical* names; a ``use(mesh, rules)`` context resolves
                 them to mesh axes (no-op outside the context, so the same
                 model runs unsharded).
  sharding     — logical axes per parameter, mesh rules per architecture
                 (EP vs TP arbitration, GQA head divisibility), batch-axis
                 selection, and NamedSharding trees for params/caches.
  collectives  — FRSZ2-compressed cross-pod gradient all-reduce
                 (``compressed_pmean``), the neighbor halo exchange for
                 banded SpMV (``halo_exchange``), and wire-byte accounting
                 (``reduce_bytes`` / ``halo_bytes`` / ``gather_bytes``).
  context      — :class:`~repro.dist.context.DistContext`: the solver's
                 norm/reduction hook (local vs psum-over-axis), threaded
                 through the GMRES cycle so the whole device-resident
                 driver runs inside ``shard_map``.
"""
from repro.dist import act_sharding, collectives, context, sharding
from repro.dist.act_sharding import constrain
from repro.dist.collectives import (
    compressed_pmean,
    gather_bytes,
    halo_bytes,
    halo_exchange,
    halo_wire_spec,
    pmean_bytes,
    reduce_bytes,
)
from repro.dist.context import DistContext
from repro.dist.sharding import (
    batch_axes,
    cache_shardings,
    driver_partition_specs,
    logical_axes,
    mesh_rules,
    param_shardings,
)

__all__ = [
    "act_sharding",
    "collectives",
    "context",
    "sharding",
    "constrain",
    "compressed_pmean",
    "gather_bytes",
    "halo_bytes",
    "halo_exchange",
    "halo_wire_spec",
    "pmean_bytes",
    "reduce_bytes",
    "DistContext",
    "batch_axes",
    "cache_shardings",
    "driver_partition_specs",
    "logical_axes",
    "mesh_rules",
    "param_shardings",
]
