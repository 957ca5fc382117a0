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
    the original exactly); the program trims the returned ``x`` back;
  * the while_loop state's partition specs come from
    :func:`repro.dist.sharding.driver_partition_specs` — ``x`` sharded,
    history buffers and scalars replicated; each restart cycle allocates
    its Krylov store inside the ``shard_map``, one local slab a device.

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

One path builds every sharded solve: ``gmres(..., shard=P)``
(:func:`sharded_gmres`, every sharding option) and the multi-chip layout
of ``solve_program`` (:func:`sharded_program`: contiguous slabs, halo
SpMV, plain transport) both plan, resolve the pipeline
(:func:`repro.solver.gmres._resolve`) and look up one cached program in
:func:`_sharded_setup`, built by :func:`_build_sharded_solve` as
``solve(b, x0, operand)`` with ``x`` leaving the padded coordinates
inside the program; both place ``b`` and ``x0`` by slab.  The same plan
and pipeline therefore compile once, whichever entry point asks.
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.dist.sharding import (
    block_driver_partition_specs,
    driver_partition_specs,
    vector_partition_spec,
)
from repro.solver.block import _block_device_solve_fn, _block_results
from repro.solver.gmres import (
    _cached,
    _device_result,
    _device_solve_fn,
    _permuted_precond,
    _resolve,
)
from repro.sparse.csr import CSR
from repro.sparse.plan import plan_operator
from repro.sparse.shard import partition_matvec

__all__ = ["sharded_gmres", "sharded_program"]

_TRANSPORTS = ("plain", "compressed", "compressed+norms")

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
    solves skip it).  The solve builds through :func:`_sharded_setup`, as
    :func:`sharded_program` does, so the same plan and pipeline share one
    compiled program.
    """
    if transport not in _TRANSPORTS:
        raise ValueError(f"unknown shard transport {transport!r}; "
                         f"expected one of {_TRANSPORTS}")
    if method not in ("vmap", "block"):
        raise ValueError(f"unknown batched method {method!r}; "
                         f"expected one of ('vmap', 'block')")
    if method == "block" and not batched:
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
    if x0 is not None and jnp.shape(x0) != b.shape:
        raise ValueError(f"x0 shape {jnp.shape(x0)} != b shape {b.shape}")

    plan, pipe, solve, operand = _sharded_setup(
        A, b, p_dev, reorder, partition_mode, pgrid, storage, policy,
        precond, ortho, m, max_iters, target_rrn, arith_dtype, eta,
        transport, axis_name, method, batched)
    states = solve(*_place(plan, pipe, b, x0, axis_name, batched), operand)
    if not batched:
        return _device_result(states)
    if method == "block":
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
    (:func:`repro.sparse.shard.partition_matvec`) and plain transport;
    the program is ``gmres(A, b, shard=n_shards, shard_matvec="halo",
    reorder="none")``'s, from the same cache entry.  ``args`` are ``(b,
    x0, operand)``: ``b`` and ``x0`` padded and placed by slab, the
    operand the cached slabs of the operator, placed from the host; the
    CSR's own arrays then move to the host
    (:meth:`~repro.sparse.csr.CSR.to_host`), so that no chip holds the
    whole operator.  The state's ``x`` is in the operator's order; each
    chip allocates its slab of the Krylov store inside the program.

    Host spans (inside ``gmres.solve_program``): ``gmres.plan`` around the
    plan and the pipeline, ``gmres.lookup`` around the compiled-solve
    cache, ``gmres.layout`` around the placement of ``b`` and ``x0``.
    """
    plan, pipe, solve, operand = _sharded_setup(
        A, b, n_shards, "none", "halo", None, storage, policy, precond,
        ortho, m, max_iters, target_rrn, arith_dtype, eta, "plain",
        axis_name)
    if isinstance(A, CSR):
        A.to_host()              # each chip holds its slab: free the rest
    with jax.profiler.TraceAnnotation("gmres.layout"):
        return solve, _place(plan, pipe, b, x0, axis_name) + (operand,)


def _sharded_setup(A, b, n_shards, reorder, partition_mode, pgrid, storage,
                   policy, precond, ortho, m, max_iters, target_rrn,
                   arith_dtype, eta, transport, axis_name, method="vmap",
                   batched=False):
    """The one set-up of a sharded solve: the plan, the pipeline over the
    plan's slabs (:func:`repro.solver.gmres._resolve`) and the cached
    program ``solve(b, x0, operand)``.  Returns ``(plan, pipe, solve,
    operand)``."""
    with jax.profiler.TraceAnnotation("gmres.plan"):
        plan, precond = _plan_and_precond(A, n_shards, reorder,
                                          partition_mode, precond, pgrid)
        if plan.n != b.shape[-1]:
            raise ValueError(f"b has trailing dim {b.shape[-1]} but the "
                             f"operator is {plan.n}x{plan.n}")
        pipe = _resolve(plan.operator, b, storage, policy, m, arith_dtype,
                        None, precond, ortho, target_rrn,
                        block=method == "block", axis_name=axis_name,
                        rows=(plan.n_local, plan.n_pad), transport=transport)
    with jax.profiler.TraceAnnotation("gmres.lookup"):
        solve, operand, _ = _cached(
            _SHARDED_CACHE, _SHARDED_CACHE_SIZE, (plan.operator, None, plan),
            pipe, ("sharded", batched, method, m, max_iters, float(eta),
                   float(target_rrn), plan.n_shards, axis_name, transport),
            lambda: _build_sharded_solve(plan, pipe, batched, method, m,
                                         max_iters, eta, target_rrn,
                                         axis_name, transport != "plain"))
    return plan, pipe, solve, operand


def _place(plan, pipe, b, x0, axis_name, batched=False):
    """``b`` and ``x0`` (zeros by default) in the solve's coordinates and
    arithmetic dtype, placed by slab.

    ``embed()`` permutes into solve coordinates *and* zero-pads in one
    step (the block3d layout interleaves pad slots inside device chunks,
    so permute-then-tail-pad would scatter real entries into pad slots).
    """
    ad = pipe.accs[0].arith_dtype
    mesh = Mesh(np.asarray(jax.devices()[:plan.n_shards]), (axis_name,))
    slabs = NamedSharding(mesh, vector_partition_spec(axis_name, batched))
    b = jax.device_put(plan.embed(b).astype(ad), slabs)
    if x0 is None:
        return b, jnp.zeros(b.shape, ad, device=slabs)
    return b, jax.device_put(plan.embed(x0).astype(ad), slabs)


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


def _build_sharded_solve(plan, pipe, batched, method, m, max_iters, eta,
                         target_rrn, axis_name, compressed_halo):
    """Partition the operator over the plan's slabs and build the program
    ``solve(b, x0, operand) -> state``, the state's ``x`` in the
    operator's order (``plan.extract`` inside the program).  Returns
    ``(solve, operand)``."""
    mesh = Mesh(np.asarray(jax.devices()[:plan.n_shards]), (axis_name,))
    operand, op_specs, local_mv = partition_matvec(
        plan=plan, axis_name=axis_name, mesh=mesh,
        compressed_halo=compressed_halo)
    # the lossy (compressed-halo) transport serves only the cycle-internal
    # matvecs; the explicit residual recomputations always ride an exact
    # exchange, else the codec error floors the attainable rrn (same split
    # as lossy basis storage vs exact arithmetic in CB-GMRES itself)
    run = jax.jit(_sharded_fn(mesh, op_specs, local_mv, local_mv.exact,
                              batched, pipe.accs, pipe.policy, m, max_iters,
                              eta, target_rrn, pipe.ortho, pipe.precond,
                              pipe.dist, axis_name, method))

    def solve(b, x0, op):
        state = run(op, b, x0)
        return dict(state, x=plan.extract(state["x"]))

    return jax.jit(solve), operand


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
        state_specs = driver_partition_specs(axis_name, batched=batched)
    return jax.shard_map(run, mesh=mesh,
                         in_specs=(op_specs, vec_spec, vec_spec),
                         out_specs=state_specs, axis_names={axis_name},
                         check_vma=False)
