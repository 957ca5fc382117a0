"""Logical-axis sharding: per-parameter axes, per-arch mesh rules, batch axes.

The contract mirrors the classic logical-axis-rules design (t5x/flax):

  * :func:`logical_axes` walks a parameter pytree and names each dim with a
    *logical* axis ("vocab", "heads", "kv_heads", "mlp", "experts") or
    ``None`` — purely structural, mesh-independent;
  * :func:`mesh_rules` maps logical names to *mesh* axes for one
    (architecture, mesh) pair, arbitrating expert-parallel vs
    tensor-parallel and dropping axes that do not divide (MQA's single KV
    head never shards; 8 experts never shard over a 16-way model axis);
  * :func:`param_shardings` / :func:`cache_shardings` combine the two into
    ``NamedSharding`` trees for jit in/out shardings;
  * :func:`batch_axes` picks the data-parallel mesh axes ("pod", "data")
    whose product divides the global batch.

Rules are deliberately tiny: every decision is a divisibility check, so the
same code serves the 1-device CPU tests and the 512-device dry-run matrix.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "logical_axes",
    "mesh_rules",
    "batch_axes",
    "param_shardings",
    "cache_shardings",
    "basis_partition_specs",
    "basis_shardings",
    "block_driver_partition_specs",
    "driver_partition_specs",
    "vector_partition_spec",
]


def _mesh_shape(mesh) -> dict:
    return dict(mesh.shape)


# ---------------------------------------------------------------------------
# logical axes per parameter
# ---------------------------------------------------------------------------

# parent container names that distinguish the two meanings of wg/wi/wo
_ATTN_PARENTS = {"attn", "cross", "shared_attn"}
_MOE_PARENTS = {"moe"}


def _axes_for(path: tuple[str, ...], leaf) -> tuple:
    """Logical axis names for one parameter, aligned to its shape.

    Positions are assigned from the *trailing* dims so the optional leading
    scanned-layer axis (and MoE's expert axis) fall out naturally.
    """
    nd = leaf.ndim
    key = path[-1]
    parents = set(path[:-1])
    ax: list = [None] * nd

    def put(offset_from_end: int, name: str):
        i = nd - offset_from_end
        if 0 <= i < nd:
            ax[i] = name

    if key == "embed":
        put(2, "vocab")
    elif key == "unembed":
        put(1, "vocab")
    elif key == "router":
        put(1, "experts")
    elif key == "wq":
        put(1, "heads")
    elif key in ("wk", "wv"):
        put(1, "kv_heads")
    elif key in ("wg", "wi", "wo") and parents & _MOE_PARENTS:
        put(3, "experts")
        put(1 if key != "wo" else 2, "mlp")
    elif key == "wo" and parents & _ATTN_PARENTS:
        put(2, "heads")
    elif key in ("wg", "wi"):
        put(1, "mlp")
    elif key == "wo":
        put(2, "mlp")
    elif key in ("in_proj", "dt_proj", "conv_w"):
        put(1, "mlp")                       # SSM inner dim reuses the TP axis
    elif key in ("x_proj", "out_proj"):
        put(2, "mlp")
    elif key == "A_log" and nd >= 3:
        put(2, "mlp")                       # mamba1: (L, d_inner, N)
    # everything else (norms, biases, gates, small state) stays replicated
    return tuple(ax)


def logical_axes(params) -> Any:
    """Pytree of per-dim logical axis tuples, matching ``params``' structure."""

    def visit(path, leaf):
        keys = tuple(
            p.key if hasattr(p, "key") else str(p) for p in path
        )
        return _axes_for(keys, leaf)

    return jax.tree_util.tree_map_with_path(visit, params)


# ---------------------------------------------------------------------------
# mesh rules per architecture
# ---------------------------------------------------------------------------


def _divides(n: int, size: int) -> bool:
    return n > 0 and size > 0 and n % size == 0


def mesh_rules(cfg, mesh) -> dict:
    """logical-name -> mesh-axis (or None) for one (arch, mesh) pair.

    Arbitration: expert parallelism wins the "model" axis when the expert
    count divides it (llama4's 16 experts on a 16-way axis); otherwise the
    FFN inner dim shards as tensor parallelism (mixtral's 8 experts do not
    divide 16, so its wide d_ff shards instead).  Heads/KV-heads/vocab each
    shard iff they divide — MQA (1 KV head) always replicates KV.
    """
    msz = _mesh_shape(mesh).get("model", 1)
    E = getattr(cfg, "num_experts", 0)
    ep = _divides(E, msz)
    inner = cfg.d_ff if cfg.d_ff else getattr(cfg, "d_inner", 0)
    return {
        "experts": "model" if ep else None,
        "mlp": "model" if (not ep and _divides(inner, msz)) else None,
        "heads": "model" if _divides(cfg.num_heads, msz) else None,
        "kv_heads": "model" if _divides(cfg.num_kv_heads, msz) else None,
        "vocab": "model" if _divides(cfg.vocab_size, msz) else None,
    }


def batch_axes(mesh, B: int) -> tuple:
    """Data-parallel mesh axes whose combined size divides ``B`` (greedy)."""
    shape = _mesh_shape(mesh)
    axes = []
    size = 1
    for a in ("pod", "data"):
        s = shape.get(a, 1)
        if s > 1 and B % (size * s) == 0:
            axes.append(a)
            size *= s
    return tuple(axes)


# ---------------------------------------------------------------------------
# NamedSharding trees
# ---------------------------------------------------------------------------


def _named(mesh, rules, ax_tuple):
    return NamedSharding(mesh, P(*[rules.get(a) if a else None
                                   for a in ax_tuple]))


def param_shardings(cfg, params, mesh):
    """NamedSharding tree for a parameter pytree (abstract or concrete)."""
    rules = mesh_rules(cfg, mesh)
    axes = logical_axes(params)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    ax_leaves = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree_util.tree_unflatten(
        treedef, [_named(mesh, rules, ax) for ax in ax_leaves]
    )


def basis_partition_specs(store, axis: str = "basis"):
    """PartitionSpec tree for a Krylov basis *store*: split along the
    vector (n) dimension, rows replicated.

    Every storage format keeps the row axis first and the (possibly
    blocked) vector axis second — native ``(m, n)``, FRSZ2 codes
    ``(m, nb * bs)``, FRSZ2 exps ``(m, nb)`` — so sharding dim 1 of every
    ``ndim >= 2`` leaf splits each basis vector across devices while
    keeping compressed blocks intact (``n`` must split on block
    boundaries, i.e. ``n_local`` a multiple of the block size).  Used with
    ``jax.shard_map`` in/out specs around a ``sharded:<fmt>`` accessor.
    """

    def visit(leaf):
        spec = [None] * leaf.ndim
        if leaf.ndim >= 2:
            spec[1] = axis
        return P(*spec)

    return jax.tree.map(visit, store)


def vector_partition_spec(axis: str = "basis", batched: bool = False) -> P:
    """Spec of one row-partitioned solve vector (``b``, ``x0``, ``x``).

    The vector dim is always the trailing one: ``(n,)`` plain or ``(k, n)``
    with an unsharded batch of right-hand sides in front (the
    vmap-inside-shard_map composition).  Centralized here so the sharded
    driver and any future consumer cannot disagree with
    :func:`driver_partition_specs`' ``x`` entry.
    """
    return P(None, axis) if batched else P(axis)


def driver_partition_specs(axis: str = "basis", batched: bool = False):
    """PartitionSpec tree for the device driver's *full* state dict.

    The device-resident GMRES driver's ``lax.while_loop`` state (see
    ``repro.solver.gmres._device_solve_fn``) runs end to end inside
    ``shard_map``; this gives the matching out_specs:

      * ``x`` — the solution vector, row-partitioned over ``axis``.
        Vectors enter the sharded driver in **plan-embed coordinates**
        (``OperatorPlan.embed``: the optional RCM permutation composed
        with the 3-D block layout's padded-space permutation for
        ``matvec_mode="block3d"``), so a contiguous ``P(axis)`` split
        lands each device exactly on its plan chunk;
      * ``hist`` / ``rst`` / ``cycle_len`` and every scalar (``total``,
        ``cycles``, ``restarts``, ``converged``, ``stagnated``, ``rrn``,
        ``prev_last``, ``nbytes``, ``op_reads``, ``steps``, ``spmvs``) —
        device-invariant, replicated.

    The Krylov store is no part of it: each restart cycle allocates its
    own, inside ``shard_map``, so each device holds the local slab of
    every Krylov vector and nothing leaves the program.  ``batched=True``
    prepends an unsharded batch dim to every spec, matching a ``vmap``
    applied *inside* the ``shard_map`` (the multi-device multi-RHS
    composition).
    """
    specs = dict(
        x=P(axis),
        total=P(), cycles=P(), restarts=P(), converged=P(),
        stagnated=P(), rrn=P(), prev_last=P(), nbytes=P(),
        op_reads=P(), hist=P(), rst=P(), steps=P(), spmvs=P(),
        cycle_len=P(),
    )
    if batched:
        specs = jax.tree.map(lambda p: P(None, *tuple(p)), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return specs


def block_driver_partition_specs(accs, axis: str = "basis"):
    """PartitionSpec tree for the *block* device driver's state dict.

    The block driver (``repro.solver.block._block_device_solve_fn``) keeps
    one shared basis of block vectors; its state differs from the scalar
    driver's in shape, not in sharding intent:

      * ``x`` — the ``(p, n)`` solution block: RHS rows replicated, vector
        dim row-partitioned over ``axis`` (same composition as
        :func:`vector_partition_spec` with ``batched=True``);
      * ``stores`` — block rows are flattened to one ``p * n_local`` row
        per Krylov index, so :func:`basis_partition_specs` applies
        unchanged (each accessor's ``empty()`` already builds the local
        chunk);
      * everything else — per-column ``(p,)`` stats (``total``,
        ``converged``, ``rrn``), scalars (``blocks``, ``cycles``,
        ``restarts``, ``stagnated``, ``prev_last``, ``nbytes``,
        ``op_reads``) and the ``(steps, p)`` histories — replicated.

    Unlike the scalar driver there is no ``batched`` flag: the block axis
    *is* the batch, carried inside each state leaf rather than by an outer
    ``vmap``.  One halo exchange per block matvec serves all ``p`` RHS —
    under ``matvec_mode="block3d"`` that is one *batched face* exchange
    per block step (the round ``ppermute``s batch over the RHS axis inside
    ``halo_exchange_3d``), not ``p`` separate exchanges.
    """
    store_specs = tuple(
        basis_partition_specs(jax.eval_shape(acc.empty), axis)
        for acc in accs
    )
    return dict(
        x=P(None, axis),
        stores=store_specs,
        total=P(), blocks=P(), cycles=P(), restarts=P(), converged=P(),
        stagnated=P(), rrn=P(), prev_last=P(), nbytes=P(),
        op_reads=P(), hist=P(), rst=P(),
    )


def basis_shardings(store, mesh, axis: str = "basis"):
    """NamedSharding tree for a basis store (see
    :func:`basis_partition_specs`)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), basis_partition_specs(store, axis)
    )


def cache_shardings(cfg, cache, mesh, B: int):
    """NamedSharding tree for a decode cache: shard the batch dim only.

    Cache leaves are ``(B,)`` (lengths) or ``(L, B, ...)`` stacked per
    layer; the batch dim is the unique dim of size ``B`` in the leading two
    positions.  Everything else is replicated — KV heads may not divide
    (MQA) and compressed code layouts must stay contiguous.
    """
    b_axes = batch_axes(mesh, B)
    bspec = tuple(b_axes) if b_axes else None

    def visit(leaf):
        spec = [None] * leaf.ndim
        for i in range(min(2, leaf.ndim)):
            if leaf.shape[i] == B:
                spec[i] = bspec
                break
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(visit, cache)
