"""Block-GMRES: one shared Krylov basis for a whole batch of right-hand sides.

``gmres_batched(method="vmap")`` solves p systems in p *independent*
Krylov spaces — the operator and p separate bases are read p times per
sweep.  On a bandwidth-bound solver (the paper's premise) that forfeits
the obvious amortization: the block-Krylov cycle here (Clark et al.,
"Pushing Memory Bandwidth Limitations Through Efficient Implementations
of Block-Krylov Space Solvers on GPUs") carries **one** basis of block
vectors ``V (m+1, p, n)``, so every Arnoldi sweep applies the operator to
a block (one operator read batched over p columns) and reads the shared
basis once for all p right-hand sides.  Compounding that with compressed
block-row storage (FRSZ2 through the unchanged ``StorageFormat``
protocol, see :class:`~repro.core.accessor.BlockBasisAccessor`) stacks
both of the paper's traffic cuts.

Algorithm per restart cycle (block analogue of ``repro.solver.gmres``):

  1. rank-revealing QR of the residual block (:func:`~repro.solver.
     pipeline.block_qr`) — converged right-hand sides enter as zero
     columns and **deflate** (zero basis row, zero couplings), as do
     linearly-dependent residuals;
  2. block Arnoldi: ``W = A M^{-1} V_j`` (one vmapped operator
     application), blocked MGS/CGS-2 against the shared basis (one einsum
     per sweep), QR of the orthogonalized block with deflation;
  3. the stacked Hessenberg is *banded* (p subdiagonals): the least
     squares reduces by p adjacent Givens rotations per column
     (``_block_apply_prior`` / ``_block_triangularize`` in
     ``repro.solver.gmres``), giving a per-column implicit residual
     estimate each block step;
  4. restart on the explicit block residual, per-column convergence,
     shared stagnation guard.

Both drivers mirror ``repro.solver.gmres`` decision-for-decision: the
device driver runs the whole restart loop as one jitted
``lax.while_loop`` (multi-level precision policies dispatch through
``lax.switch``); the host driver is the python-looped parity oracle.
Sharded (``gmres_batched(..., shard=P, method="block")``, running through
``repro.solver.sharded``), the block matvec batches over the RHS axis
*inside* the collective: one halo exchange — one set of face
``ppermute``s under ``matvec_mode="block3d"`` — per block step serves the
whole batch, so the wire cost per RHS shrinks by ``1/p`` exactly like the
basis reads.

Accounting: ``bytes_read`` prices the *shared* basis once per sweep and
``op_reads`` counts modelled full operator passes (one per block matvec,
not p); each returned :class:`~repro.solver.gmres.GmresResult` carries
its ``1/p`` share so summing over the batch reproduces the batch total —
the same summation semantics as the vmap path, which is what
``benchmarks/block_gmres.py`` compares.

The hot contractions (``block_dots``/``block_combine`` in the block
orthogonalizers and the solution update) dispatch through the
``StorageFormat`` protocol: FRSZ2 storage with ``use_kernels`` routes them
through the fused decode-inside-contraction Pallas kernels
(``repro.kernels.frsz2_dot``), so the compressed block basis is expanded
in-register per tile instead of materializing the decoded ``(m+1, p, n)``
array in HBM each sweep (the jaxpr-level fusion proof lives in
``tests/test_block_kernels.py``, built on :func:`build_block_solve`).
``bytes_read`` is unchanged by the route — both read the same compressed
rows — and the stage-3 traffic audit
(``repro.analysis.traffic.run_local_traffic``) holds it to exact equality
through the fused path.
"""
from __future__ import annotations

from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.context import LOCAL
from repro.solver.gmres import (
    _HOST_KERNEL_CACHE,
    _HOST_KERNEL_CACHE_SIZE,
    _SOLVE_CACHE,
    _SOLVE_CACHE_SIZE,
    _TINY,
    GmresResult,
    _block_apply_prior,
    _block_solve_and_update,
    _block_triangularize,
    _cached,
    _cycle_row_reads,
    _prepare,
    _resolve,
)
from repro.solver.pipeline import block_qr

__all__ = ["gmres_block"]


def _block_cycle(bmv, acc, bn_safe, store, W0, eta, target, ortho, precond,
                 dist=LOCAL):
    """One block-GMRES(m) cycle.  ``W0 (p, n)`` is the residual block
    (converged columns already zeroed by the caller; they deflate in the
    initial QR and stay dead for the cycle: a zero basis vector maps to a
    zero matvec, which re-deflates every step).

    Returns ``(store, R, G, est, extra_rows)``: the rotated stacked
    Hessenberg ``R ((m+1)p, mp)`` (upper triangular in its leading
    block), the rotated rhs ``G ((m+1)p, p)``, the per-block-step
    per-column implicit residual estimates ``est (m, p)``, and the exact
    count of extra basis block rows swept by conditional
    re-orthogonalization passes.

    ``dist`` routes reductions exactly as in the scalar cycle, so the
    same code runs row-partitioned inside ``shard_map`` — where one block
    matvec is still one halo exchange for all p right-hand sides.
    """
    mb = acc.m - 1
    p = acc.p
    ad = acc.arith_dtype
    mp = mb * p

    Q0, S, _ = block_qr(W0, dist)
    store = acc.write_block(store, 0, Q0)

    R0 = jnp.zeros((mp + p, mp), ad)
    G0 = jnp.zeros((mp + p, p), ad).at[:p, :].set(S)
    cs0 = jnp.ones((mp, p), ad)      # identity rotations: replay needs no mask
    sn0 = jnp.zeros((mp, p), ad)
    est0 = jnp.full((mb, p), jnp.inf, ad)
    rows = jnp.arange(mb + 1)

    def body(j, carry):
        store, R, G, cs, sn, est, extra_rows, alive = carry
        Vj = acc.read_block(store, j)
        W = bmv(Vj).astype(ad)
        w_pre = dist.col_norms(W)

        mask = rows <= j
        Q, H, T, fired = ortho(acc, store, W, mask, eta, dist, w_pre)
        extra_rows = extra_rows + jnp.where(alive, fired * (j + 1), 0)
        store = acc.write_block(store, j + 1, Q)

        # stacked Hessenberg column slab of this step: H rows <= j, then T
        Hfull = jnp.where(mask[:, None, None], H, 0.0).at[j + 1].set(T)
        slab = Hfull.reshape(mp + p, p)
        jp = j * p
        slab = _block_apply_prior(slab, cs, sn, jp, p)
        slab, G_new, csn, snn, gtail = _block_triangularize(slab, G, jp, p)
        est_j = jnp.sqrt(jnp.sum(jnp.square(gtail), axis=0)) / bn_safe

        R_new = jax.lax.dynamic_update_slice(R, slab, (0, jp))
        cs_new = jax.lax.dynamic_update_slice(cs, csn, (jp, 0))
        sn_new = jax.lax.dynamic_update_slice(sn, snn, (jp, 0))
        R = jnp.where(alive, R_new, R)
        G = jnp.where(alive, G_new, G)
        cs = jnp.where(alive, cs_new, cs)
        sn = jnp.where(alive, sn_new, sn)
        est = est.at[j].set(
            jnp.where(alive, est_j, est[jnp.maximum(j - 1, 0)]))

        # total breakdown: every new direction deflated — no progress left
        dead = jnp.all(jnp.abs(jnp.diagonal(T)) <= _TINY)
        alive_next = alive & ~dead & jnp.any(est_j > target)
        return store, R, G, cs, sn, est, extra_rows, alive_next

    store, R, G, cs, sn, est, extra_rows, alive = jax.lax.fori_loop(
        0, mb, body,
        (store, R0, G0, cs0, sn0, est0, jnp.asarray(0, jnp.int32),
         jnp.asarray(True))
    )
    return store, R, G, est, extra_rows


def _cycle_stops(col_hit, mb: int):
    """Shared and per-column stopping points from ``col_hit (m, p)``.

    The cycle is truncated at ``j_stop`` — the first block step where
    *every* column's implicit estimate met the target (else m); each
    column's own iteration count stops at its first hit (or the shared
    stop).  Deflated/converged columns have zero estimates, so they hit
    immediately and never hold the block back.
    """
    all_hit = jnp.all(col_hit, axis=1)
    hit_any = jnp.any(all_hit)
    j_stop = jnp.where(hit_any, jnp.argmax(all_hit).astype(jnp.int32) + 1,
                       mb)
    hit_b = jnp.any(col_hit, axis=0)
    first_b = jnp.argmax(col_hit, axis=0).astype(jnp.int32) + 1
    j_stop_b = jnp.minimum(jnp.where(hit_b, first_b, j_stop), j_stop)
    return hit_any, j_stop, j_stop_b


# ---------------------------------------------------------------------------
# Device-resident block driver (one lax.while_loop, like the scalar driver)
# ---------------------------------------------------------------------------


def _block_device_solve_fn(matvec, accs, policy, m: int, max_iters: int,
                           eta: float, target_rrn: float, ortho, precond,
                           dist=LOCAL, residual_matvec=None):
    """Build the pure ``(B, X0) -> state`` block solve (jit-able).

    Mirrors ``_device_solve_fn`` with block semantics: ``max_iters``
    bounds the per-column iteration count (= block steps executed),
    ``converged``/``rrn``/``total`` are per-column, the stagnation guard
    watches the worst still-active column.  ``residual_matvec`` splits
    the exact residual operator from a possibly lossy cycle matvec, as in
    the scalar driver.
    """
    rmv = matvec if residual_matvec is None else residual_matvec
    ad = accs[0].arith_dtype
    p = accs[0].p
    n_levels = len(accs)
    row_bytes = [acc.nbytes() / acc.m for acc in accs]
    hist_cap = max_iters + m
    rst_cap = max_iters + 1
    bmv = jax.vmap(lambda v: matvec(precond.apply(v)))
    bmv_r = jax.vmap(rmv)

    def solve(B, X0):
        B = B.astype(ad)
        bn_safe = jnp.maximum(dist.col_norms(B), _TINY)
        rrn0 = dist.col_norms(B - bmv_r(X0).astype(ad)) / bn_safe

        init = dict(
            x=X0,
            stores=tuple(acc.empty() for acc in accs),
            total=jnp.zeros((p,), jnp.int32),
            blocks=jnp.asarray(0, jnp.int32),
            cycles=jnp.asarray(0, jnp.int32),
            restarts=jnp.asarray(0, jnp.int32),
            converged=jnp.zeros((p,), bool),
            stagnated=jnp.asarray(False),
            rrn=rrn0,
            prev_last=jnp.asarray(jnp.inf, ad),
            nbytes=jnp.asarray(0.0, ad),
            op_reads=jnp.asarray(1.0, ad),     # the rrn0 residual above
            hist=jnp.zeros((hist_cap, p), ad),
            rst=jnp.zeros((rst_cap, p), ad),
        )

        def cond(s):
            return ((s["blocks"] < max_iters) & ~jnp.all(s["converged"])
                    & ~s["stagnated"])

        def body(s):
            R0v = B - bmv_r(s["x"]).astype(ad)
            rr = dist.col_norms(R0v) / bn_safe
            rst = s["rst"].at[s["restarts"]].set(rr, mode="drop")
            restarts = s["restarts"] + 1
            op_head = s["op_reads"] + 1.0
            active = rr > target_rrn
            early = ~jnp.any(active)
            rr_gate = jnp.max(jnp.where(active, rr, 0.0))
            lvl = policy.level(rr_gate, s["cycles"])

            def run_cycle_at(k):
                def run(s):
                    acc = accs[k]
                    W0 = jnp.where(active[:, None], R0v, 0.0)
                    store, R, G, est, extra_rows = _block_cycle(
                        bmv, acc, bn_safe, s["stores"][k], W0, eta,
                        target_rrn, ortho, precond, dist
                    )
                    hit_any, j_stop, j_stop_b = _cycle_stops(
                        est <= target_rrn, m)
                    x = _block_solve_and_update(acc, store, R, G, j_stop,
                                                s["x"], precond)
                    idx = s["blocks"] + jnp.arange(m)
                    hist = s["hist"].at[idx].set(est, mode="drop")
                    blocks = s["blocks"] + j_stop
                    total = s["total"] + jnp.where(active, j_stop_b, 0)
                    cycles = s["cycles"] + 1
                    rrn = dist.col_norms(B - bmv_r(x).astype(ad)) / bn_safe
                    conv = rrn <= target_rrn
                    last = jnp.max(jnp.where(
                        active, est[jnp.maximum(j_stop - 1, 0)], 0.0))
                    stag = (
                        ~jnp.all(conv) & hit_any & (j_stop >= m)
                        & (cycles > 4)
                        & (jnp.abs(last - s["prev_last"])
                           <= 1e-8 + 1e-2 * jnp.abs(s["prev_last"]))
                    )
                    nbytes = s["nbytes"] + (
                        _cycle_row_reads(j_stop, ortho.passes,
                                         extra_rows).astype(ad)
                        * row_bytes[k])
                    op_reads = op_head + j_stop.astype(ad) + 1.0
                    stores = tuple(
                        store if i == k else s["stores"][i]
                        for i in range(n_levels)
                    )
                    return dict(
                        x=x, stores=stores, total=total, blocks=blocks,
                        cycles=cycles, restarts=restarts, converged=conv,
                        stagnated=stag, rrn=rrn, prev_last=last,
                        nbytes=nbytes, op_reads=op_reads, hist=hist,
                        rst=rst,
                    )
                return run

            def run_cycle(s):
                if n_levels == 1:
                    return run_cycle_at(0)(s)
                return jax.lax.switch(
                    lvl, [run_cycle_at(k) for k in range(n_levels)], s)

            def skip_cycle(s):
                return dict(
                    s, restarts=restarts, converged=rr <= target_rrn,
                    rrn=rr, rst=rst, op_reads=op_head,
                )

            return jax.lax.cond(early, skip_cycle, run_cycle, s)

        return jax.lax.while_loop(cond, body, init)

    return solve


def _block_results(state) -> list[GmresResult]:
    """Trim the block state into one GmresResult per right-hand side.

    ``bytes_read``/``op_reads`` carry each column's 1/p share of the
    batch's shared traffic (summing over results gives the batch total —
    vmap summation semantics); ``rrn_history`` rows are block steps (each
    advances every still-active column by one Krylov direction).
    """
    blocks = int(state["blocks"])
    restarts = int(state["restarts"])
    p = state["rrn"].shape[0]
    share_bytes = float(state["nbytes"]) / p
    share_ops = float(state["op_reads"]) / p
    hist = np.asarray(state["hist"][:blocks])
    rst = np.asarray(state["rst"][:restarts])
    return [
        GmresResult(
            x=state["x"][b],
            rrn=float(state["rrn"][b]),
            iterations=int(state["total"][b]),
            converged=bool(state["converged"][b]),
            rrn_history=hist[:, b].copy(),
            restart_rrns=rst[:, b].copy(),
            restarts=restarts,
            bytes_read=share_bytes,
            stagnated=bool(state["stagnated"]),
            op_reads=share_ops,
        )
        for b in range(p)
    ]


# ---------------------------------------------------------------------------
# Host-looped block driver (parity oracle)
# ---------------------------------------------------------------------------


def _gmres_block_host(pipe, B, X, m, max_iters, target_rrn, eta,
                      op) -> list[GmresResult]:
    """Python restart loop mirroring ``_block_device_solve_fn``
    decision-for-decision (same jitted cycle, numpy restart logic).
    ``B`` and ``X`` are in the arithmetic dtype; ``op`` is the operator's
    ``(A, user_matvec, plan)``, the key of the cached kernels."""
    accs, policy, ortho, precond, _, matvec = pipe
    ad = accs[0].arith_dtype
    p = accs[0].p
    bmv = jax.vmap(lambda v: matvec(precond.apply(v)))
    bmv_r = jax.vmap(matvec)
    bn_safe = jnp.maximum(jnp.linalg.norm(B, axis=1), _TINY)

    # ``bn_safe`` is a jit argument, not a closure constant — see
    # _gmres_host: a closed-over per-solve array would recompile the cycle
    # for every new right-hand-side block.
    def make_cycle(acc):
        return jax.jit(lambda store, W0, bn: _block_cycle(
            bmv, acc, bn, store, W0, eta, target_rrn, ortho, precond))

    def make_update(acc):
        return jax.jit(lambda store, R, G, j_stop, X_: _block_solve_and_update(
            acc, store, R, G, j_stop, X_, precond))

    def kernels_for(lvl):
        acc = accs[lvl]
        return _cached(_HOST_KERNEL_CACHE, _HOST_KERNEL_CACHE_SIZE, op, pipe,
                       ("host", lvl, float(eta), float(target_rrn)),
                       lambda: ((make_cycle(acc), make_update(acc)),))[0]

    kernels: dict[int, tuple] = {}
    stores: dict[int, Any] = {}

    history: list[np.ndarray] = []
    restart_rrns: list[np.ndarray] = []
    total = np.zeros((p,), np.int64)
    blocks = 0
    cycles = 0
    converged = np.zeros((p,), bool)
    stagnated = False
    nbytes = 0.0
    op_reads = 1.0               # parity with the device driver's rrn0
    prev_last = np.inf
    rrn = None

    while blocks < max_iters and not converged.all() and not stagnated:
        R0v = B - bmv_r(X).astype(ad)
        rr = np.asarray(jnp.linalg.norm(R0v, axis=1) / bn_safe)
        restart_rrns.append(rr)
        op_reads += 1.0
        rrn = rr
        active = rr > target_rrn
        if not active.any():
            converged = rr <= target_rrn
            break
        lvl = int(policy.level(float(np.max(np.where(active, rr, 0.0))),
                               cycles))
        if lvl not in kernels:
            kernels[lvl] = kernels_for(lvl)
            stores[lvl] = accs[lvl].empty()
        cycle, update = kernels[lvl]
        W0 = jnp.where(jnp.asarray(active)[:, None], R0v, 0.0)
        stores[lvl], R, G, est, extra_rows = cycle(stores[lvl], W0, bn_safe)
        est_np = np.asarray(est)
        col_hit = est_np <= target_rrn
        all_hit = col_hit.all(axis=1)
        hit = np.nonzero(all_hit)[0]
        j_stop = int(hit[0]) + 1 if hit.size else m
        hit_b = col_hit.any(axis=0)
        first_b = np.where(hit_b, col_hit.argmax(axis=0) + 1, j_stop)
        j_stop_b = np.minimum(first_b, j_stop)
        X = update(stores[lvl], R, G, jnp.asarray(j_stop), X)
        history.append(est_np[:j_stop])
        blocks += j_stop
        total += np.where(active, j_stop_b, 0)
        cycles += 1
        nbytes += _cycle_row_reads(j_stop, ortho.passes, int(extra_rows)) * (
            accs[lvl].nbytes() / accs[lvl].m)
        op_reads += float(j_stop) + 1.0
        rrn = np.asarray(jnp.linalg.norm(B - bmv_r(X).astype(ad), axis=1)
                         / bn_safe)
        converged = rrn <= target_rrn
        last = float(np.max(np.where(active, est_np[max(j_stop - 1, 0)],
                                     0.0)))
        if (not converged.all() and hit.size and j_stop >= m
                and cycles > 4
                and abs(last - prev_last) <= 1e-8 + 1e-2 * abs(prev_last)):
            stagnated = True
        prev_last = last

    if rrn is None:              # max_iters < 1: loop never entered
        rrn = np.asarray(jnp.linalg.norm(B - bmv_r(X).astype(ad), axis=1)
                         / bn_safe)

    hist_all = (np.concatenate(history, axis=0) if history
                else np.zeros((0, p)))
    rsts = (np.stack(restart_rrns) if restart_rrns
            else np.zeros((0, p)))
    share_bytes = nbytes / p
    share_ops = op_reads / p
    return [
        GmresResult(
            x=X[b],
            rrn=float(rrn[b]),
            iterations=int(total[b]),
            converged=bool(converged[b]),
            rrn_history=hist_all[:, b].copy(),
            restart_rrns=rsts[:, b].copy(),
            restarts=len(restart_rrns),
            bytes_read=share_bytes,
            stagnated=stagnated,
            op_reads=share_ops,
        )
        for b in range(p)
    ]


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def gmres_block(
    A: Any,
    B: jax.Array,
    *,
    X0: jax.Array | None = None,
    storage: Any = None,
    policy: Any = None,
    precond: Any = None,
    ortho: Any = "mgs",
    m: int = 100,
    max_iters: int = 20000,
    target_rrn: float = 1e-14,
    arith_dtype: Any = None,
    eta: float = 0.7071067811865475,
    matvec: Callable | None = None,
    driver: str = "device",
    reorder: str = "auto",
) -> list[GmresResult]:
    """Solve A X[b] = B[b] for all p right-hand sides with block-GMRES.

    The front door is ``gmres_batched(..., method="block")``; see the
    module docstring for the algorithm and :func:`repro.solver.gmres.
    gmres` for the shared pipeline arguments (``ortho`` names a *block*
    orthogonalizer here — the same ``"mgs"``/``"cgs2"`` choices).
    ``max_iters`` bounds the per-column iteration count.
    """
    if B.ndim != 2:
        raise ValueError(f"B must be (batch, n), got {B.shape}")
    if driver not in ("device", "host"):
        raise ValueError(f"unknown driver {driver!r}; "
                         f"expected one of ('device', 'host')")
    plan, A, B, X0, pipe = _prepare(A, B, X0, storage, policy, precond,
                                    ortho, m, arith_dtype, matvec,
                                    target_rrn, reorder, block=True)
    op = (A, matvec, plan)
    if driver == "host":
        results = _gmres_block_host(pipe, B, X0, m, max_iters, target_rrn,
                                    eta, op)
    else:
        solve = _cached(
            _SOLVE_CACHE, _SOLVE_CACHE_SIZE, op, pipe,
            ("block", m, max_iters, float(eta), float(target_rrn)),
            lambda: (jax.jit(_block_device_solve_fn(
                pipe.matvec, pipe.accs, pipe.policy, m, max_iters, eta,
                target_rrn, pipe.ortho, pipe.precond)),))[0]
        results = _block_results(solve(B, X0))
    if plan is not None:
        for r in results:
            r.x = plan.unpermute(r.x)
    return results


def build_block_solve(A, B, *, storage=None, policy=None, precond=None,
                      ortho="mgs", m: int = 30, max_iters: int = 2000,
                      target_rrn: float = 1e-10, arith_dtype=None,
                      eta: float = 0.7071067811865475, matvec=None):
    """Un-jitted ``(B, X0) -> state`` block solve plus accessors.

    The block-driver counterpart of
    :func:`repro.solver.gmres.build_device_solve`: the jaxpr/eval_shape
    surface the trace audit checks
    :func:`repro.dist.sharding.block_driver_partition_specs` against.
    """
    pipe = _resolve(A, B, storage, policy, m, arith_dtype, matvec, precond,
                    ortho, target_rrn, block=True)
    solve = _block_device_solve_fn(pipe.matvec, pipe.accs, pipe.policy, m,
                                   max_iters, eta, target_rrn, pipe.ortho,
                                   pipe.precond)
    return solve, pipe.accs
