"""The full device-resident GMRES driver inside ``jax.shard_map``.

``repro.solver.gmres`` builds the whole restart loop as one jitted
``lax.while_loop`` (driver="device").  This module runs that *same* solve
function end to end across devices: every vector (``b``, ``x``, the Krylov
basis rows, the residual) is row-partitioned along the vector dim over a
1-D mesh, and

  * the basis lives in ``sharded:<fmt>`` storage — each device holds the
    local chunk of every Krylov vector; the orthogonalization dot products
    reduce over the axis (optionally as FRSZ2 codes on the wire,
    :func:`repro.dist.collectives.compressed_psum`);
  * vector norms become psum-of-local-squares through the
    :class:`~repro.dist.context.DistContext` threaded into the cycle;
  * the matvec is row-partitioned (neighbor halo exchange for banded
    operators, gathered operand or a replicated fallback otherwise) and
    all host-side prep — optional RCM reordering (``reorder=``),
    zero-padding, bandwidth probing, mode arbitration (forced with
    ``partition_mode=``) — comes from one content-cached
    :class:`~repro.sparse.plan.OperatorPlan` that
    :func:`repro.sparse.shard.partition_matvec` consumes;
  * vector dims that do not divide the mesh are zero-padded to the next
    multiple (padded operator rows are masked, so the padded solve embeds
    the original exactly); the returned ``x`` is trimmed back;
  * the while_loop state's partition specs come from
    :func:`repro.dist.sharding.driver_partition_specs` — ``x`` and the
    stores sharded, history buffers and scalars replicated.

Because every reduced quantity (norms, Hessenberg entries, residual
estimates) is device-invariant after its psum, all devices take identical
restart/convergence decisions and the data-dependent control flow
(``while_loop``/``cond``/``switch``) stays in lockstep — the solve is one
SPMD program with zero host round-trips, which is exactly the paper's
bandwidth argument carried to the multi-device regime: once basis reads
are cheap, the surviving traffic is these collectives, so they ride the
same compressed transport the dots already use.

``gmres_batched(..., shard=...)`` composes the two scaling axes: the
``vmap`` over right-hand sides runs *inside* the ``shard_map``, so one XLA
program advances ``k`` systems over ``P`` devices.
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.accessor import BasisAccessor, BlockBasisAccessor, ShardedFormat
from repro.dist.context import DistContext
from repro.dist.sharding import (
    block_driver_partition_specs,
    driver_partition_specs,
    vector_partition_spec,
)
from repro.solver.block import _block_device_solve_fn, _block_results
from repro.solver.gmres import (
    _device_result,
    _device_solve_fn,
    _lru_cached,
    _operator_key,
    _permuted_precond,
)
from repro.solver.pipeline import (
    AdaptivePolicy,
    StaticPolicy,
    block_orthogonalizer_by_name,
    orthogonalizer_by_name,
    resolve_policy,
    resolve_preconditioner,
)
from repro.sparse.csr import CSR
from repro.sparse.plan import plan_operator
from repro.sparse.shard import partition_matvec

__all__ = ["sharded_gmres", "sharded_program"]

_TRANSPORTS = ("plain", "compressed", "compressed+norms")


def _wrap_policy(policy, axis_name: str, compressed_dots: bool):
    """Wrap every policy level in ShardedFormat.

    The solve's ``shard_transport`` argument is the single authority on
    the collective wire format: formats that arrive already sharded (e.g.
    ``storage="sharded:frsz2_32"``, whose builder defaults to compressed
    transport) are rebuilt onto the requested transport and axis, so
    ``transport="plain"`` always means the documented exact-psum parity.
    """

    def wrap(fmt):
        if isinstance(fmt, ShardedFormat):
            fmt = fmt.inner
        return ShardedFormat(inner=fmt, axis_name=axis_name,
                             compressed_transport=compressed_dots)

    fmts = tuple(wrap(f) for f in policy.formats())
    if isinstance(policy, StaticPolicy):
        return StaticPolicy(fmts[0])
    if isinstance(policy, AdaptivePolicy):
        return AdaptivePolicy(levels=fmts, thresholds=policy.thresholds)
    raise ValueError(
        f"cannot shard custom policy {type(policy).__name__}: give it "
        "ShardedFormat levels explicitly")


# one compiled shard_map program per (operator, pipeline, geometry, mesh);
# the partitioned operand is cached alongside (ELL conversion is host work).
_SHARDED_CACHE: OrderedDict = OrderedDict()
_SHARDED_CACHE_SIZE = 8


def sharded_gmres(A, b, *, batched: bool = False, x0=None, storage=None,
                  policy=None, precond=None, ortho="mgs", m: int = 100,
                  max_iters: int = 20000, target_rrn: float = 1e-14,
                  arith_dtype=None, eta: float = 0.7071067811865475,
                  matvec=None, shard: int = 1, transport: str = "plain",
                  axis_name: str = "basis", partition_mode: str = "auto",
                  reorder: str = "auto", method: str = "vmap", pgrid=None):
    """Run ``gmres``/``gmres_batched`` semantics under ``shard_map``.

    Called through ``gmres(..., shard=P)`` — see that docstring.  ``b`` is
    ``(n,)``, or ``(k, n)`` with ``batched=True``; returns the matching
    :class:`~repro.solver.gmres.GmresResult` (or list of them).

    ``method="block"`` (batched only) runs the block-GMRES driver
    (:mod:`repro.solver.block`) inside the same ``shard_map``: the block
    basis rows flatten to one ``p * n_local`` chunk per device, so the
    sharded storage formats apply unchanged, and one batched halo
    exchange per block matvec serves all ``p`` right-hand sides (for the
    3-D block partition, one batched *face* exchange per block step).

    ``pgrid`` forces the ``(Px, Py, Pz)`` process-grid factorization of
    the 3-D block partition (``partition_mode="block3d"``, or considered
    by ``"auto"`` when the operator carries cell geometry).

    All host-side operator prep — optional RCM reordering, padding
    geometry, bandwidth probing, matvec-mode arbitration — comes from one
    :class:`~repro.sparse.plan.OperatorPlan` (content-cached, so repeated
    solves skip it); this driver only maps vectors through the plan and
    splices its partition into ``shard_map``.
    """
    if transport not in _TRANSPORTS:
        raise ValueError(f"unknown shard transport {transport!r}; "
                         f"expected one of {_TRANSPORTS}")
    if method not in ("vmap", "block"):
        raise ValueError(f"unknown batched method {method!r}; "
                         f"expected one of ('vmap', 'block')")
    block = method == "block"
    if block and not batched:
        raise ValueError("method='block' needs batched=True (B is (p, n))")
    if matvec is not None:
        raise ValueError(
            "shard= needs an operator with partitionable rows (CSR/ELL); "
            "a bare matvec callable cannot be row-partitioned")
    p_dev = int(shard)
    devices = jax.devices()
    if p_dev < 1 or p_dev > len(devices):
        raise ValueError(
            f"shard={p_dev} but only {len(devices)} devices are visible")

    b = jnp.asarray(b)
    n = b.shape[-1]
    plan, precond = _plan_and_precond(A, p_dev, reorder, partition_mode,
                                      precond, pgrid)
    if plan.n != n:
        raise ValueError(f"b has trailing dim {n} but the operator "
                         f"is {plan.n}x{plan.n}")
    # vector dims that do not divide the mesh shard zero-padded: padded
    # operator rows are masked (val 0), so every padded vector entry stays
    # an exact zero through the whole solve and x trims back losslessly
    n_pad, n_local = plan.n_pad, plan.n_local
    if arith_dtype is None:
        arith_dtype = b.dtype

    compressed_dots = transport in ("compressed", "compressed+norms")
    policy = _wrap_policy(
        resolve_policy(policy, storage, arith_dtype, target_rrn, m),
        axis_name, compressed_dots)
    if block:
        p_rhs = int(b.shape[0])
        accs = tuple(
            BlockBasisAccessor(fmt=f, m=m + 1, p=p_rhs, n=n_local,
                               arith_dtype=arith_dtype)
            for f in policy.formats()
        )
        ortho_obj = block_orthogonalizer_by_name(ortho)
    else:
        accs = tuple(
            BasisAccessor(fmt=f, m=m + 1, n=n_local,
                          arith_dtype=arith_dtype)
            for f in policy.formats()
        )
        ortho_obj = orthogonalizer_by_name(ortho)
    precond_obj = resolve_preconditioner(precond, plan.operator).shard_local(
        axis_name, n_local, n_pad)
    dist = DistContext(axis_name=axis_name,
                       compressed_norms=transport == "compressed+norms")

    solve, operand = _cached_sharded_solve(
        plan, batched, accs, policy, m, max_iters, eta, target_rrn,
        ortho_obj, precond_obj, dist, axis_name, compressed_dots, method)

    # embed() permutes into solve coordinates *and* zero-pads in one step
    # (the block3d layout interleaves pad slots inside device chunks, so
    # permute-then-tail-pad would scatter real entries into pad slots)
    if x0 is None:
        x0 = jnp.zeros(b.shape, b.dtype)
    else:
        x0 = jnp.asarray(x0)
        if x0.shape != b.shape:
            raise ValueError(f"x0 shape {x0.shape} != b shape {b.shape}")
    b = plan.embed(b).astype(arith_dtype)
    x0 = plan.embed(x0).astype(arith_dtype)

    states = solve(operand, b, x0)
    states = dict(states, x=plan.extract(states["x"]))
    if not batched:
        return _device_result(states)
    if block:
        return _block_results(states)
    return [
        _device_result(jax.tree.map(lambda a: a[i], states))
        for i in range(b.shape[0])
    ]


def sharded_program(A, b, n_shards: int, *, x0=None, storage=None,
                    policy=None, precond=None, ortho="mgs", m: int = 100,
                    max_iters: int = 20000, target_rrn: float = 1e-14,
                    arith_dtype=None, eta: float = 0.7071067811865475,
                    axis_name: str = "basis"):
    """The program :func:`repro.solver.gmres.solve_program` returns for a
    solve that does not fit one chip: ``(solve, args)`` with
    ``solve(*args)`` the state of ``gmres``'s device driver, as on one
    chip.

    The operator is planned as ``n_shards`` contiguous slabs of rows in
    its own order (no reordering, no 3-D blocks; rows zero-padded to a
    multiple of ``n_shards``), with the halo SpMV
    (:func:`repro.sparse.shard.partition_matvec`).  ``args`` are ``(b,
    x0, operand)``: ``b`` and ``x0`` padded and placed by slab, the
    operand the cached slabs of the operator, placed from the host; the
    CSR's own arrays then move to the host
    (:meth:`~repro.sparse.csr.CSR.to_host`), so that no chip holds the
    whole operator.  The state's ``x`` is in the operator's order, trimmed
    to ``n`` inside the program; the Krylov stores stay sharded on the
    chips.

    Host spans (inside ``gmres.solve_program``): ``gmres.layout`` around
    the placement of ``b`` and ``x0``, ``gmres.plan`` around the plan and
    the pipeline, ``gmres.lookup`` around the compiled-solve cache.
    """
    with jax.profiler.TraceAnnotation("gmres.plan"):
        plan, precond = _plan_and_precond(A, n_shards, "none", "halo",
                                          precond)
        if arith_dtype is None:
            arith_dtype = b.dtype
        policy, accs, ortho_obj, precond_obj, dist = _slab_pipeline(
            plan.operator, plan.n_local, plan.n_pad, storage, policy,
            precond, ortho, m, target_rrn, arith_dtype, axis_name)
    with jax.profiler.TraceAnnotation("gmres.lookup"):
        solve, operand = _cached_sharded_solve(
            plan, False, accs, policy, m, max_iters, eta, target_rrn,
            ortho_obj, precond_obj, dist, axis_name, False, "vmap",
            program=True)
        if isinstance(A, CSR):
            A.to_host()          # each chip holds its slab: free the rest
    with jax.profiler.TraceAnnotation("gmres.layout"):
        mesh = Mesh(np.asarray(jax.devices()[:n_shards]), (axis_name,))
        slabs = NamedSharding(mesh, vector_partition_spec(axis_name))
        b = jax.device_put(plan.embed(b).astype(arith_dtype), slabs)
        if x0 is None:
            x0 = jnp.zeros(plan.n_pad, arith_dtype, device=slabs)
        else:
            x0 = jax.device_put(plan.embed(x0).astype(arith_dtype), slabs)
    return solve, (b, x0, operand)


def _slab_pipeline(operator, n_local, n_pad, storage, policy, precond,
                   ortho, m, target_rrn, arith_dtype, axis_name):
    """The pipeline of :func:`sharded_program` over slabs of ``n_local``
    rows: the policy's formats sharded (plain transport), their accessors,
    the orthogonalizer, the preconditioner's local part and the norms'
    context."""
    policy = _wrap_policy(
        resolve_policy(policy, storage, arith_dtype, target_rrn, m),
        axis_name, False)
    accs = tuple(BasisAccessor(fmt=f, m=m + 1, n=n_local,
                               arith_dtype=arith_dtype)
                 for f in policy.formats())
    precond = resolve_preconditioner(precond, operator).shard_local(
        axis_name, n_local, n_pad)
    return (policy, accs, orthogonalizer_by_name(ortho), precond,
            DistContext(axis_name=axis_name))


def _plan_and_precond(A, p_dev, reorder, partition_mode, precond,
                      pgrid=None):
    """Plan the operator and carry the preconditioner through the plan's
    permutation.

    ``reorder="auto"`` declines a permutation the preconditioner cannot
    follow (a bare callable hook, or a Preconditioner without
    ``permuted``): auto only buys wire bytes, so an un-permutable
    preconditioner outweighs it and the solve proceeds unreordered.  The
    same logic declines an *auto-picked* block3d layout (its padded-space
    permutation needs the same preconditioner conjugation).  Explicit
    ``reorder="rcm"`` / ``partition_mode="block3d"`` propagate the error
    instead.
    """
    plan = plan_operator(A, p_dev, reorder=reorder,
                         matvec_mode=partition_mode, pgrid=pgrid)
    try:
        return plan, _permuted_precond(precond, plan)
    except (ValueError, NotImplementedError):
        auto_block = plan.matvec_mode == "block3d" and partition_mode != \
            "block3d"
        if reorder != "auto" and not auto_block:
            raise
        plan = plan_operator(A, p_dev,
                             reorder="none" if reorder == "auto" else reorder,
                             matvec_mode=partition_mode, pgrid=pgrid,
                             allow_block3d=False)
        return plan, _permuted_precond(precond, plan)


def _build_sharded_solve(plan, batched, accs, policy, m, max_iters, eta,
                         target_rrn, ortho, precond, dist, axis_name,
                         compressed_halo, method):
    mesh = Mesh(np.asarray(jax.devices()[:plan.n_shards]), (axis_name,))
    operand, op_specs, local_mv = partition_matvec(
        plan=plan, axis_name=axis_name, mesh=mesh,
        compressed_halo=compressed_halo)
    # the lossy (compressed-halo) transport serves only the cycle-internal
    # matvecs; the explicit residual recomputations always ride an exact
    # exchange, else the codec error floors the attainable rrn (same split
    # as lossy basis storage vs exact arithmetic in CB-GMRES itself)
    sm = _sharded_fn(mesh, op_specs, local_mv, local_mv.exact, batched,
                     accs, policy, m, max_iters, eta, target_rrn, ortho,
                     precond, dist, axis_name, method)
    return jax.jit(sm), operand


def _sharded_fn(mesh, op_specs, local_mv, local_rmv, batched, accs, policy,
                m, max_iters, eta, target_rrn, ortho, precond, dist,
                axis_name, method):
    """The restart driver under ``shard_map`` over ``mesh``:
    ``(operand, b, x0) -> state``, each device contracting its rows with
    ``local_mv`` (``local_rmv`` for the explicit residuals).  Needs no
    data, so a described topology can compile it."""

    if method == "block":
        # the block driver batches the matvec itself (jax.vmap inside the
        # solve fn), so the per-block halo exchange ships all p boundary
        # strips in one batched ppermute — the amortization the block
        # method exists for
        def run(op, B_loc, X0_loc):
            mv = lambda v: local_mv(op, v)  # noqa: E731
            rmv = lambda v: local_rmv(op, v)  # noqa: E731
            fn = _block_device_solve_fn(mv, accs, policy, m, max_iters,
                                        eta, target_rrn, ortho, precond,
                                        dist, residual_matvec=rmv)
            return fn(B_loc, X0_loc)

        vec_spec = vector_partition_spec(axis_name, batched=True)
        state_specs = block_driver_partition_specs(accs, axis_name)
    else:
        def solve_local(op, b_loc, x0_loc):
            mv = lambda v: local_mv(op, v)  # noqa: E731
            rmv = lambda v: local_rmv(op, v)  # noqa: E731
            fn = _device_solve_fn(mv, accs, policy, m, max_iters, eta,
                                  target_rrn, ortho, precond, dist,
                                  residual_matvec=rmv)
            return fn(b_loc, x0_loc)

        if batched:
            def run(op, B_loc, X0_loc):
                return jax.vmap(lambda bb, xx: solve_local(op, bb, xx))(
                    B_loc, X0_loc)
        else:
            run = solve_local

        vec_spec = vector_partition_spec(axis_name, batched=batched)
        state_specs = driver_partition_specs(accs, axis_name,
                                             batched=batched)
    return jax.shard_map(run, mesh=mesh,
                         in_specs=(op_specs, vec_spec, vec_spec),
                         out_specs=state_specs, axis_names={axis_name},
                         check_vma=False)


def _cached_sharded_solve(plan, batched, accs, policy, m, max_iters, eta,
                          target_rrn, ortho, precond, dist, axis_name,
                          compressed_halo, method, program: bool = False):
    """The compiled sharded solve and its operand (cached): ``solve(operand,
    b, x0)``, or with ``program`` ``solve(b, x0, operand)`` with ``x``
    trimmed to ``n`` inside, as :func:`sharded_program` returns it."""
    pins: tuple = ()

    def make_key():
        nonlocal pins
        # the plan's key already folds in the operator content fingerprint,
        # the executed reorder, and the resolved matvec mode; operators
        # without a fingerprint fall back to identity keying (pinned)
        op_key, pins = _operator_key(plan.operator, None, plan)
        pins = pins + (precond,)
        return (op_key, batched, method, getattr(accs[0], "p", 0),
                policy.spec(), ortho.name, precond.spec(),
                dist.spec(), accs[0].m, accs[0].n,
                jnp.dtype(accs[0].arith_dtype).name, m, max_iters,
                float(eta), float(target_rrn), plan.n_shards, axis_name,
                compressed_halo, program)

    def build():
        solve, operand = _build_sharded_solve(
            plan, batched, accs, policy, m, max_iters, eta, target_rrn,
            ortho, precond, dist, axis_name, compressed_halo, method)
        if program:
            run, n = solve, plan.n

            def solve(b, x0, op):
                state = run(op, b, x0)
                return dict(state, x=state["x"][:n])

            solve = jax.jit(solve)
        return solve, operand, pins

    ent = _lru_cached(_SHARDED_CACHE, _SHARDED_CACHE_SIZE, make_key, build)
    return ent[0], ent[1]
