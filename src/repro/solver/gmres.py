"""Restarted GMRES(m) with a compressed Krylov basis (CB-GMRES, paper Fig. 1).

Faithful to the paper's algorithmic formulation:

  * Arnoldi with the orthogonalization expressed as the two Accessor hot
    loops ``h = V_j^T w`` (dots) and ``w -= V_j h`` (combine);
  * conditional re-orthogonalization when ``h_{j+1,j} < eta * ||w_pre||``
    (Fig. 1 steps 6-10, the "twice is enough" criterion);
  * Givens-rotation least squares on the Hessenberg matrix, giving the
    *implicit* residual estimate ``|g_{j+1}|`` per inner iteration;
  * restart after ``m`` vectors: explicit residual recomputation (this is
    what produces the correction jumps in paper Fig. 9);
  * the Krylov basis ``V`` lives in an arbitrary storage format behind a
    :class:`~repro.core.accessor.BasisAccessor` — any format implementing
    the :class:`~repro.core.accessor.StorageFormat` protocol: float64/
    float32/float16 (CB-GMRES [1]), FRSZ2 (this paper), or mixed-precision.
    All arithmetic is performed in ``arith_dtype`` (f64 on CPU for
    paper-faithful runs, f32 on TPU).

Cycle pipeline
--------------

The cycle is assembled from three pluggable stages (see
:mod:`repro.solver.pipeline`):

  * ``ortho`` — :class:`~repro.solver.pipeline.Orthogonalizer`: ``"mgs"``
    (seed scheme, conditional reorth) or ``"cgs2"`` (two unconditional
    batched passes through the fused ``StorageFormat.dots`` path);
  * ``precond`` — :class:`~repro.solver.pipeline.Preconditioner`, applied
    as *right* preconditioning ``A M^{-1}`` inside the jitted cycle of
    both drivers: ``"jacobi"``, a callable hook, or any object with
    ``apply``;
  * ``policy`` — :class:`~repro.solver.pipeline.PrecisionPolicy`: the
    storage format per restart cycle.  The device driver dispatches the
    cycle through ``lax.switch`` on the restart residual, and each cycle
    allocates the store of its level, so an adaptive ``float64 -> frsz2_32
    -> frsz2_16`` schedule still runs as a single XLA program.

Every result carries ``bytes_read`` — the modelled basis read traffic
(rows touched by read_row/dots/combine/update times the active format's
per-row storage), the quantity the paper's bandwidth argument is about.

Drivers
-------

Two drivers share the same jitted cycle/update kernels:

  * ``driver="device"`` (default) — the **device-resident** driver: the
    entire restart loop (cycles + explicit residual recomputation +
    stagnation guard) is a single jitted ``lax.while_loop``, so a full
    solve is one XLA program with zero host round-trips.  Convergence
    history is accumulated into fixed device buffers and pulled to the
    host exactly once at the end.  This is what the paper's premise
    requires: CB-GMRES is bandwidth-bound, so per-cycle host syncs
    (``np.asarray``/`float()` on the residual estimate) must not dominate
    wall time.
  * ``driver="host"`` — the seed host-looped driver (one device sync per
    restart cycle), kept as the parity oracle; ``tests/test_solver.py``
    asserts both produce identical iteration counts and final RRN.

``gmres_batched`` vmaps the device-resident solve over a batch of
right-hand sides: one XLA program advances all systems, each with its own
restart schedule (the while_loop runs until the *last* system converges;
finished systems are masked).

Set-up
------

Every entry point (``gmres`` on either driver, ``gmres_batched``,
``repro.solver.block.gmres_block``, ``solve_program`` on one chip or
many, ``repro.solver.sharded.sharded_gmres`` and the ``build_*_solve``
introspection functions) reaches its compiled program by one path: the
unsharded entries plan and permute in :func:`_prepare`, the sharded ones
in ``repro.solver.sharded._sharded_setup``; both resolve the pipeline in
:func:`_resolve` and look the program up under the one key
:func:`_solve_key` (:func:`_cached`).  A pipeline field is therefore
resolved, and keyed, in one place.

The inner cycle is a single ``lax.fori_loop`` over a fixed-capacity basis
buffer with row masking, so the solver traces once per
(problem-size, m, pipeline) combination.  The buffer lives inside one
cycle: the cycle writes each row before any trip reads it, so nothing in
the store outlives the cycle, and no restart loop carries it (carried,
XLA copies the whole store on every inner trip).
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from collections.abc import Callable
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accessor import (
    HIGHEST,
    BasisAccessor,
    BlockBasisAccessor,
    ShardedFormat,
)
from repro.dist.context import LOCAL, DistContext
from repro.solver.pipeline import (
    AdaptivePolicy,
    StaticPolicy,
    block_orthogonalizer_by_name,
    orthogonalizer_by_name,
    resolve_policy,
    resolve_preconditioner,
)
from repro.sparse.csr import CSR, operator_matvec

__all__ = ["GmresResult", "gmres", "gmres_batched", "cb_gmres", "solve_program"]

_TINY = 1e-300


@dataclasses.dataclass
class GmresResult:
    """One solve's answer and counters.

    ``steps``, ``spmvs`` and ``cycle_lengths`` count what the restart
    driver ran, not what the algorithm needs: every inner-loop trip of a
    cycle, masked trips after convergence included, and every operator
    application.  The block driver (:mod:`repro.solver.block`) leaves them
    at their defaults.
    """

    x: jax.Array                 # final solution approximation
    rrn: float                   # true relative residual norm at exit
    iterations: int              # total inner iterations executed
    converged: bool
    rrn_history: np.ndarray      # implicit residual estimate per iteration
    restart_rrns: np.ndarray     # explicit RRN measured at each restart
    restarts: int
    bytes_read: float = 0.0      # modelled basis read traffic (bytes)
    stagnated: bool = False      # stopped by the stagnation guard, not
                                 # convergence or the iteration budget
    op_reads: float = 0.0        # modelled full passes over the operator
                                 # (Arnoldi matvecs + explicit residuals);
                                 # block results carry their 1/p share of
                                 # the batch's shared passes
    steps: int = 0               # inner-loop trips run, masked ones included
    spmvs: int = 0               # operator applications run
    cycle_lengths: np.ndarray = dataclasses.field(  # j_stop of each cycle run
        default_factory=lambda: np.zeros((0,), np.int32))


def _givens(a, b):
    """Stable Givens rotation: returns (c, s) with [c s; -s c]ᵀ [a;b] = [r;0]."""
    denom = jnp.sqrt(a * a + b * b)
    safe = jnp.where(denom > 0, denom, 1.0)
    c = jnp.where(denom > 0, a / safe, 1.0)
    s = jnp.where(denom > 0, b / safe, 0.0)
    return c, s


def _cycle(matvec: Callable, acc: BasisAccessor, b_norm, w0, beta,
           eta: float, target: float, ortho, precond, dist=LOCAL):
    """One GMRES(m) cycle.  w0 = r0 (unnormalized); beta = ||r0||.

    The cycle allocates its own zero-filled store (``acc.empty()``) and
    writes row ``j + 1`` before any trip reads it.  Returns (store, R, g,
    rrn_est, extra_rows, steps) where store is that Krylov basis, R the
    rotated Hessenberg (upper triangular in its leading block), g the
    rotated rhs, rrn_est the per-inner-iteration implicit residual
    estimate, extra_rows the exact count of basis rows swept by extra
    (conditional) orthogonalization passes: each live iteration j whose
    orthogonalizer fired contributes its j+1 live rows — folded into the
    bytes_read accounting — and steps the inner-loop trips run, one
    operator application each, masked trips included.

    ``dist`` routes vector norms: local (default) or psum-of-local-squares
    when the cycle runs row-partitioned inside ``shard_map``.
    """
    m = acc.m - 1
    ad = acc.arith_dtype

    store = acc.write_row(acc.empty(), 0, w0 / jnp.maximum(beta, _TINY))

    R0 = jnp.zeros((m + 1, m), ad)
    g0 = jnp.zeros((m + 1,), ad).at[0].set(beta)
    cs0 = jnp.zeros((m,), ad)
    sn0 = jnp.zeros((m,), ad)
    est0 = jnp.full((m,), jnp.inf, ad)
    rows = jnp.arange(m + 1)

    def body(j, carry):
        store, R, g, cs, sn, est, extra_rows, steps, alive = carry
        v = acc.read_row(store, j)
        w = matvec(precond.apply(v)).astype(ad)
        w_pre = dist.norm(w)

        mask = rows <= j
        w, h, hj1, fired = ortho(acc, store, w, mask, eta, dist, w_pre)
        extra_rows = extra_rows + jnp.where(alive, fired * (j + 1), 0)

        breakdown = hj1 <= 1e-30 * w_pre + _TINY
        hj1_safe = jnp.maximum(hj1, _TINY)
        vnew = w / hj1_safe
        store = acc.write_row(store, j + 1, vnew)

        with jax.named_scope("givens"):
            # Hessenberg column = [h_{1:j,j}; h_{j+1,j}], then rotations
            col = jnp.where(mask, h, 0.0)
            col = col.at[j + 1].set(hj1)

            def rot_body(i, col):
                a = col[i]
                bb = col[i + 1]
                live = i < j
                c = jnp.where(live, cs[jnp.minimum(i, m - 1)], 1.0)
                s = jnp.where(live, sn[jnp.minimum(i, m - 1)], 0.0)
                col = col.at[i].set(c * a + s * bb)
                col = col.at[i + 1].set(-s * a + c * bb)
                return col

            col = jax.lax.fori_loop(0, j, rot_body, col)
            c, s = _givens(col[j], col[j + 1])
            col = col.at[j].set(c * col[j] + s * col[j + 1])
            col = col.at[j + 1].set(0.0)
            gj = g[j]
            g = g.at[j].set(c * gj)
            g = g.at[j + 1].set(-s * gj)

            R = R.at[:, j].set(jnp.where(alive, col, R[:, j]))
            cs = cs.at[j].set(c)
            sn = sn.at[j].set(s)
            resid = jnp.abs(g[j + 1]) / b_norm
            est = est.at[j].set(
                jnp.where(alive, resid, est[jnp.maximum(j - 1, 0)]))
        alive_next = alive & (~breakdown) & (resid > target)
        return store, R, g, cs, sn, est, extra_rows, steps + 1, alive_next

    zero = jnp.asarray(0, jnp.int32)
    store, R, g, cs, sn, est, extra_rows, steps, alive = jax.lax.fori_loop(
        0, m, body,
        (store, R0, g0, cs0, sn0, est0, zero, zero, jnp.asarray(True))
    )
    return store, R, g, est, extra_rows, steps


def _solve_and_update(acc: BasisAccessor, store, R, g, j_stop, x0, precond):
    """y = argmin ||beta e1 - H y|| (truncated at j_stop), x = x0 + M^{-1}V_m y."""
    m = acc.m - 1
    ad = acc.arith_dtype
    with jax.named_scope("update"):
        active = jnp.arange(m) < j_stop
        # Back substitution on the leading (j_stop, j_stop) block of R.
        Rm = jnp.where(active[None, :] & active[:, None], R[:m, :m], 0.0)
        # anchor the fill literals to the arithmetic dtype: a bare
        # where(mask, 1.0, 0.0) has no array operand and materializes the
        # full (m, m) select in weak f64 under x64
        Rm = Rm + jnp.where(jnp.eye(m, dtype=bool) & ~active[:, None],
                            jnp.ones((), ad), jnp.zeros((), ad))
        gm = jnp.where(active, g[:m], 0.0)

        def back(i, y):
            jj = m - 1 - i
            s = gm[jj] - jnp.dot(Rm[jj], y, precision=HIGHEST)
            yi = s / Rm[jj, jj]
            return y.at[jj].set(jnp.where(active[jj], yi, 0.0))

        y = jax.lax.fori_loop(0, m, back, jnp.zeros((m,), ad))
        ypad = jnp.concatenate([y, jnp.zeros((1,), ad)])
        dx = precond.apply(acc.combine(store, ypad, jnp.arange(m + 1) < j_stop))
        return x0 + dx


def _cycle_row_reads(j_stop, passes: int, extra_rows=0):
    """Basis rows touched by one cycle of ``j_stop`` useful iterations.

    Per iteration j: 1 read_row + ``passes`` sweeps of dots+combine over the
    j+1 live rows; plus the solution-update combine over j_stop rows.
    ``extra_rows`` is the exact row count swept by conditional extra passes
    (MGS's re-orthogonalization): the cycle reports ``sum of j+1 over the
    live iterations that fired``, so late-firing reorths are charged their
    true (larger) sweep, not an amortized average.
    """
    return j_stop * (2 + passes * (j_stop + 1)) + extra_rows


# ---------------------------------------------------------------------------
# Block Hessenberg least squares (block-GMRES, see repro.solver.block)
# ---------------------------------------------------------------------------
#
# With blocks of p coupled right-hand sides the stacked Hessenberg
# ``Hbar ((m+1)p, mp)`` is *banded* upper Hessenberg: column ``c`` has
# exactly p subdiagonal entries (rows c+1..c+p — the H block of its step
# plus the upper-triangular QR factor T of the new block).  The least
# squares ``min ||G - Hbar Y||`` therefore still reduces by Givens
# rotations, p per column instead of one, each pairing the subdiagonal
# entry *directly with the pivot row* ``(c, c+k)``, k = p..1.
#
# Pivot pairing (rather than the textbook adjacent-pair chain) is what
# makes deflation safe: a deflated basis direction is a zero vector, so
# its Hessenberg row and column are identically zero, and a rotation
# whose non-pivot entry is zero is the identity — dead rows never absorb
# entries or rhs mass, the live sub-system reduces exactly as scalar
# GMRES would, and the implicit per-column residual estimate stays exact.
# (An adjacent chain instead *swaps* live entries up into dead pivot
# slots, stranding rhs mass where no column can reduce it.)
#
# Rotations are stored per column as ``cs/sn (mp, p)`` — entry ``[c, k]``
# acts on rows ``(c, c+p-k)``, applied in k order — and initialized to
# the identity (cs=1, sn=0) so replaying them over a traced column range
# needs no masking.


def _block_apply_prior(slab, cs, sn, jp, p: int):
    """Apply all stored rotations of columns ``< jp`` to a new column slab.

    ``slab ((m+1)p, q)`` is the stacked Hessenberg column block of the
    current step.  Column ``c``'s rotations only touch rows ``c..c+p``, so
    each replay is a ``(p+1)``-row window at a dynamic offset; the loop
    bound ``jp`` is traced (fori_loop lowers to while_loop).
    """
    q = slab.shape[1]

    def apply_col(c, slab):
        wnd = jax.lax.dynamic_slice(slab, (c, 0), (p + 1, q))
        for k in range(p):
            r1 = p - k                   # rotation k pairs rows (c, c+p-k)
            a, b = wnd[0], wnd[r1]
            cc, ss = cs[c, k], sn[c, k]
            wnd = wnd.at[0].set(cc * a + ss * b)
            wnd = wnd.at[r1].set(-ss * a + cc * b)
        return jax.lax.dynamic_update_slice(slab, wnd, (c, 0))

    return jax.lax.fori_loop(0, jp, apply_col, slab)


def _block_triangularize(slab, G, jp, p: int):
    """Annihilate the subdiagonal band of the step's new columns.

    After :func:`_block_apply_prior`, rows ``jp..jp+2p-1`` of the slab
    hold the still-unreduced window (prior rotations never reach below row
    ``jp+p``).  Local column ``k`` has subdiagonal entries in window rows
    ``k+1..k+p``; each is killed by a rotation pairing it directly with
    the pivot row ``k`` (see the banner comment — this keeps deflated
    rows identically zero), applied to the remaining slab columns and to
    the rotated rhs ``G``.

    Returns ``(slab, G, csn, snn, gtail)``: the new rotations ``(p, p)``
    in the storage layout of :func:`_block_apply_prior` (``[k, p-i]``
    acts on window rows ``(k, k+i)``), and ``gtail = G[jp+p : jp+2p]`` —
    the unreduced rhs rows whose per-column norms are the implicit
    residual estimates of this step (the block analogue of ``|g_{j+1}|``;
    rhs mass only ever moves down within a pivot's band, so the p-row
    tail holds all of it).  Deflated (all-zero) columns produce identity
    rotations via the zero-safe :func:`_givens`, so the band reduction is
    breakdown-free.
    """
    q = slab.shape[1]
    W = jax.lax.dynamic_slice(slab, (jp, 0), (2 * p, q))
    G2 = jax.lax.dynamic_slice(G, (jp, 0), (2 * p, G.shape[1]))
    csn = jnp.ones((p, p), slab.dtype)
    snn = jnp.zeros((p, p), slab.dtype)
    for k in range(p):
        for i in range(p, 0, -1):
            r1 = k + i
            c, s = _givens(W[k, k], W[r1, k])
            a, b = W[k], W[r1]
            W = W.at[k].set(c * a + s * b)
            W = W.at[r1].set(-s * a + c * b)
            ga, gb = G2[k], G2[r1]
            G2 = G2.at[k].set(c * ga + s * gb)
            G2 = G2.at[r1].set(-s * ga + c * gb)
            csn = csn.at[k, p - i].set(c)
            snn = snn.at[k, p - i].set(s)
        W = W.at[k + 1:, k].set(0.0)     # exact zeros below the diagonal
    slab = jax.lax.dynamic_update_slice(slab, W, (jp, 0))
    G = jax.lax.dynamic_update_slice(G, G2, (jp, 0))
    return slab, G, csn, snn, G2[p:]


def _block_solve_and_update(acc, store, R, G, j_stop, X0, precond):
    """Block least squares: ``Y = argmin ||G - R Y||`` truncated at
    ``j_stop`` block columns, then ``X = X0 + M^{-1} (V Y)``.

    ``R ((m+1)p, mp)`` is the rotated (upper-triangular) stacked
    Hessenberg, ``G ((m+1)p, p)`` the rotated rhs.  Deflated directions
    show up as exactly-zero diagonal entries (their whole column is zero:
    a zero basis vector propagates zero inner products); they are excluded
    from the back substitution (zero coefficient), which is precisely the
    minimization over the deflated subspace.
    """
    mb = acc.m - 1
    p = acc.p
    mp = mb * p
    ad = acc.arith_dtype
    idx = jnp.arange(mp)
    active = idx < j_stop * p
    Rm = jnp.where(active[None, :] & active[:, None], R[:mp, :mp], 0.0)
    diag_ok = jnp.abs(jnp.diagonal(Rm)) > _TINY
    solved = active & diag_ok
    eye = jnp.eye(mp, dtype=bool)
    # typed fill literals — see the note in _solve_and_update
    Rm = Rm + jnp.where(eye & ~solved[:, None],
                        jnp.ones((), ad), jnp.zeros((), ad))
    Gm = jnp.where(active[:, None], G[:mp], 0.0)

    def back(i, Y):
        jj = mp - 1 - i
        s = Gm[jj] - jnp.matmul(Rm[jj], Y, precision=HIGHEST)
        yi = s / Rm[jj, jj]
        return Y.at[jj].set(jnp.where(solved[jj], yi, 0.0))

    Y = jax.lax.fori_loop(0, mp, back, jnp.zeros((mp, p), ad))
    Ypad = jnp.concatenate([Y.reshape(mb, p, p), jnp.zeros((1, p, p), ad)])
    dX = acc.block_combine(store, Ypad, jnp.arange(mb + 1) < j_stop)
    return X0 + jax.vmap(precond.apply)(dX)


# ---------------------------------------------------------------------------
# Shared setup
# ---------------------------------------------------------------------------


class _Pipeline(NamedTuple):
    """A solve's resolved pipeline (:func:`_resolve`)."""

    accs: tuple          # one accessor per policy level
    policy: Any
    ortho: Any
    precond: Any
    dist: Any            # DistContext: LOCAL, or the mesh axis's reductions
    matvec: Any          # the SpMV; None on the sharded path, whose
                         # builder partitions the operator


def _resolve(A, b, storage, policy, m, arith_dtype, matvec, precond, ortho,
             target_rrn, *, block=False, axis_name=None, rows=None,
             transport="plain") -> _Pipeline:
    """The one pipeline resolver of every solve entry point.

    ``b`` (an array, or its shape and dtype) gives the default arithmetic
    dtype, the row count and, with ``block``, the block width ``p =
    b.shape[0]`` (block accessors and a block orthogonalizer).  With
    ``axis_name`` the solve runs row-partitioned inside ``shard_map``:
    ``rows = (n_local, n_pad)``, every policy level is wrapped in
    :class:`~repro.core.accessor.ShardedFormat` on ``transport``, the
    preconditioner keeps its local part and the norms reduce over the
    axis.
    """
    if arith_dtype is None:
        arith_dtype = b.dtype
    policy = resolve_policy(policy, storage, arith_dtype, target_rrn, m)
    precond = resolve_preconditioner(precond, A)
    n, dist = b.shape[-1], LOCAL
    if axis_name is not None:
        n, n_pad = rows
        policy = _wrap_policy(policy, axis_name, transport != "plain")
        precond = precond.shard_local(axis_name, n, n_pad)
        dist = DistContext(axis_name=axis_name,
                           compressed_norms=transport == "compressed+norms")
    elif matvec is None:
        matvec = operator_matvec(A)
    if block:
        accs = tuple(BlockBasisAccessor(fmt=f, m=m + 1, p=b.shape[0], n=n,
                                        arith_dtype=arith_dtype)
                     for f in policy.formats())
        ortho = block_orthogonalizer_by_name(ortho)
    else:
        accs = tuple(BasisAccessor(fmt=f, m=m + 1, n=n,
                                   arith_dtype=arith_dtype)
                     for f in policy.formats())
        ortho = orthogonalizer_by_name(ortho)
    return _Pipeline(accs, policy, ortho, precond, dist, matvec)


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


def _plan_unsharded(A, reorder: str, user_matvec):
    """Resolve ``reorder`` for a single-device solve; a plan or ``None``.

    ``"auto"`` is a no-op off the sharded path — the permutation only buys
    wire bytes, and an unsharded solve has no wire.  ``"rcm"`` forces the
    permutation (the solve then runs on ``plan.operator`` in permuted
    coordinates; callers map ``b``/``x0`` in and ``x`` back out through
    the plan).  Plans are content-cached, so repeated solves of the same
    problem reuse the permutation and its fingerprint.
    """
    from repro.sparse.plan import REORDERS, plan_operator

    if reorder not in REORDERS:
        raise ValueError(f"unknown reorder mode {reorder!r}; "
                         f"expected one of {REORDERS}")
    if reorder != "rcm":
        return None
    if user_matvec is not None or A is None:
        raise ValueError(
            "reorder='rcm' needs an operator with an inspectable sparsity "
            "pattern (CSR/ELL); a bare matvec callable cannot be reordered")
    return plan_operator(A, 1, reorder="rcm")


def _permuted_precond(precond, plan):
    """Map a user-supplied preconditioner into the plan's coordinates."""
    from repro.solver.pipeline import Preconditioner

    if plan is None or plan.perm is None or precond is None:
        return precond
    if isinstance(precond, Preconditioner):
        return precond.permuted(plan.perm)
    if callable(precond):
        raise ValueError(
            "cannot reorder with a bare callable preconditioner hook: its "
            "coordinate convention is unknown; wrap it in a Preconditioner "
            "with permuted() or pass reorder='none'")
    return precond               # names resolve against plan.operator


# ---------------------------------------------------------------------------
# Host-looped driver (the seed driver; parity oracle for the device one)
# ---------------------------------------------------------------------------


def _gmres_host(pipe, b, x, m, max_iters, target_rrn, eta,
                op) -> GmresResult:
    """The host-looped solve of ``b`` from ``x`` (both in the arithmetic
    dtype); ``op`` is the operator's ``(A, user_matvec, plan)``, the key
    of the cached kernels."""
    accs, policy, ortho, precond, _, matvec = pipe
    arith_dtype = accs[0].arith_dtype
    b_norm = jnp.linalg.norm(b)

    # ``b_norm`` rides as a jit *argument*: closing over it would bake the
    # per-solve array into the trace as a constant, recompiling the cycle
    # for every new right-hand side (the retrace class the trace audit
    # gates on).
    def make_cycle(acc):
        return jax.jit(
            lambda w0, beta, b_norm_: _cycle(
                matvec, acc, b_norm_, w0, beta, eta, target_rrn, ortho,
                precond
            )
        )

    def make_update(acc):
        return jax.jit(
            lambda store, R, g, j_stop, x0_: _solve_and_update(
                acc, store, R, g, j_stop, x0_, precond
            )
        )

    def kernels_for(lvl):
        acc = accs[lvl]
        return _cached(_HOST_KERNEL_CACHE, _HOST_KERNEL_CACHE_SIZE, op, pipe,
                       ("host", lvl, float(eta), float(target_rrn)),
                       lambda: ((make_cycle(acc), make_update(acc)),))[0]

    # per-policy-level jitted kernels, built on first use
    kernels: dict[int, tuple] = {}

    history: list[np.ndarray] = []
    restart_rrns: list[float] = []
    total_iters = 0
    converged = False
    stagnated = False
    bytes_read = 0.0
    # operator passes: 1.0 up front for parity with the device driver's
    # eager rrn0 (the host computes that residual lazily, but both drivers
    # model the same work); +1 per loop-head residual; +j_stop modelled
    # Arnoldi matvecs and +1 explicit post-update residual per cycle.
    op_reads = 1.0
    # operator applications, counted as the device driver counts them (its
    # eager rrn0 included); inner-loop trips; each cycle's j_stop
    spmvs = 1
    steps = 0
    cycle_lengths: list[int] = []
    # rrn is (re)established at each loop head from the explicit restart
    # residual (the seed's extra up-front matvec was redundant); the
    # fallback below only runs for a zero iteration budget, keeping parity
    # with the device driver's rrn0.
    rrn = None

    while total_iters < max_iters and not converged:
        r = b - matvec(x).astype(arith_dtype)
        beta = jnp.linalg.norm(r)
        restart_rrns.append(float(beta / b_norm))
        op_reads += 1.0
        spmvs += 1
        rrn = restart_rrns[-1]
        if rrn <= target_rrn:
            converged = True
            break
        lvl = int(policy.level(restart_rrns[-1], len(restart_rrns) - 1))
        if lvl not in kernels:
            kernels[lvl] = kernels_for(lvl)
        cycle, update = kernels[lvl]
        store, R, g, est, extra_rows, trips = cycle(r, beta, b_norm)
        est_np = np.asarray(est)
        # first inner iteration that met the target (1-based count)
        hit = np.nonzero(est_np <= target_rrn)[0]
        j_stop = int(hit[0]) + 1 if hit.size else m
        # breakdown shows up as a frozen tail in est; detect via argmin
        x = update(store, R, g, jnp.asarray(j_stop), x)
        history.append(est_np[:j_stop])
        total_iters += j_stop
        steps += int(trips)
        spmvs += int(trips) + 1          # the trips and the residual below
        cycle_lengths.append(j_stop)
        bytes_read += _cycle_row_reads(j_stop, ortho.passes,
                                       int(extra_rows)) * (
            accs[lvl].nbytes() / accs[lvl].m)
        op_reads += float(j_stop) + 1.0
        rrn = float(jnp.linalg.norm(b - matvec(x).astype(arith_dtype)) / b_norm)
        if rrn <= target_rrn:
            converged = True
        elif hit.size:
            # implicit estimate said converged but explicit says no:
            # continue restarting (classic CB-GMRES behaviour — the
            # compressed basis made the estimate optimistic).
            if j_stop >= m and len(history) > 4 and np.allclose(
                history[-1][-1], history[-2][-1], rtol=1e-2
            ):
                stagnated = True
                break  # stagnation guard

    if rrn is None:        # max_iters < 1: loop never entered
        rrn = float(jnp.linalg.norm(b - matvec(x).astype(arith_dtype))
                    / b_norm)

    return GmresResult(
        x=x,
        rrn=rrn,
        iterations=total_iters,
        converged=converged,
        rrn_history=(np.concatenate(history) if history
                     else np.zeros((0,), np.float64)),
        restart_rrns=np.asarray(restart_rrns),
        restarts=len(restart_rrns),
        bytes_read=bytes_read,
        stagnated=stagnated,
        op_reads=op_reads,
        steps=steps,
        spmvs=spmvs,
        cycle_lengths=np.asarray(cycle_lengths, np.int32),
    )


# ---------------------------------------------------------------------------
# Device-resident driver: the whole restart loop is one lax.while_loop
# ---------------------------------------------------------------------------


def _device_solve_fn(matvec, accs, policy, m: int, max_iters: int,
                     eta: float, target_rrn: float, ortho, precond,
                     dist=LOCAL, residual_matvec=None):
    """Build the pure (b, x0) -> state solve function (jit/vmap-able).

    Semantics replicate ``_gmres_host`` decision-for-decision so the two
    drivers produce identical iteration counts, restart schedules, and
    residual histories (the parity test asserts this).  The returned state
    dict carries fixed-size history buffers; the host wrapper trims them.
    It also counts what the device ran, each in the loop that runs it:
    ``steps`` (inner-loop trips, masked ones included), ``spmvs`` (operator
    applications) and ``cycle_len`` (each cycle's ``j_stop``, indexed by
    ``cycles``).

    The state carries no Krylov store: each cycle allocates its own
    (:func:`_cycle`), so no loop copies it.  Multi-level precision policies
    dispatch each cycle with ``lax.switch`` on the policy's level index,
    each branch with the store of its level — the whole adaptive solve
    remains a single XLA program.

    ``dist`` distributes the solve: with an axis name bound, ``b``/``x0``
    are the device-local chunks of row-partitioned vectors, ``matvec`` must
    be a local matvec (see ``repro.sparse.shard.partition_matvec``), and
    every norm reduces over the mesh axis — the whole restart loop then
    runs inside ``shard_map`` (see ``repro.solver.sharded``).

    ``residual_matvec`` (default: ``matvec``) is the operator used for the
    explicit residual recomputations that gate restarts and convergence.
    The split mirrors CB-GMRES's central trick: the *cycle-internal*
    matvec may be lossy (a compressed halo transport perturbs Arnoldi like
    inexact Krylov — tolerable), but the residual check must apply the
    exact operator or its error becomes the convergence floor.
    """
    rmv = matvec if residual_matvec is None else residual_matvec
    ad = accs[0].arith_dtype
    n_levels = len(accs)
    row_bytes = [acc.nbytes() / acc.m for acc in accs]
    hist_cap = max_iters + m          # last cycle may overrun max_iters
    rst_cap = max_iters + 1           # one restart record per cycle + final

    def solve(b, x0):
        b = b.astype(ad)
        b_norm = dist.norm(b)
        with jax.named_scope("residual"):
            rrn0 = dist.norm(b - rmv(x0).astype(ad)) / b_norm

        init = dict(
            x=x0,
            total=jnp.asarray(0, jnp.int32),
            cycles=jnp.asarray(0, jnp.int32),
            restarts=jnp.asarray(0, jnp.int32),
            converged=jnp.asarray(False),
            stagnated=jnp.asarray(False),
            rrn=rrn0,
            prev_last=jnp.asarray(jnp.inf, ad),
            nbytes=jnp.asarray(0.0, ad),
            op_reads=jnp.asarray(1.0, ad),     # the rrn0 residual above
            hist=jnp.zeros((hist_cap,), ad),
            rst=jnp.zeros((rst_cap,), ad),
            steps=jnp.asarray(0, jnp.int32),
            spmvs=jnp.asarray(1, jnp.int32),   # the rrn0 residual above
            cycle_len=jnp.zeros((rst_cap,), jnp.int32),
        )

        def cond(s):
            return (s["total"] < max_iters) & ~s["converged"] & ~s["stagnated"]

        def body(s):
            with jax.named_scope("residual"):
                r = b - rmv(s["x"]).astype(ad)
                beta = dist.norm(r)
                rr = beta / b_norm
            rst = s["rst"].at[s["restarts"]].set(rr, mode="drop")
            restarts = s["restarts"] + 1
            op_head = s["op_reads"] + 1.0   # the loop-head residual above
            spmv_head = s["spmvs"] + 1
            early = rr <= target_rrn        # restart residual already there
            lvl = policy.level(rr, s["cycles"])

            def run_cycle_at(k):
                def run(s):
                    acc = accs[k]
                    store, R, g, est, extra_rows, steps = _cycle(
                        matvec, acc, b_norm, r, beta, eta, target_rrn,
                        ortho, precond, dist
                    )
                    hit = est <= target_rrn
                    hit_any = jnp.any(hit)
                    j_stop = jnp.where(
                        hit_any, jnp.argmax(hit).astype(jnp.int32) + 1, m
                    )
                    x = _solve_and_update(acc, store, R, g, j_stop, s["x"],
                                          precond)
                    idx = s["total"] + jnp.arange(m)
                    hist = s["hist"].at[idx].set(est, mode="drop")
                    total = s["total"] + j_stop
                    cycles = s["cycles"] + 1
                    with jax.named_scope("residual"):
                        rrn = dist.norm(b - rmv(x).astype(ad)) / b_norm
                    conv = rrn <= target_rrn
                    last = est[jnp.maximum(j_stop - 1, 0)]
                    # stagnation guard (host: np.allclose(last, prev, 1e-2))
                    stag = (
                        ~conv & hit_any & (j_stop >= m) & (cycles > 4)
                        & (jnp.abs(last - s["prev_last"])
                           <= 1e-8 + 1e-2 * jnp.abs(s["prev_last"]))
                    )
                    nbytes = s["nbytes"] + (
                        _cycle_row_reads(j_stop, ortho.passes,
                                         extra_rows).astype(ad)
                        * row_bytes[k])
                    op_reads = op_head + j_stop.astype(ad) + 1.0
                    return dict(
                        x=x, total=total, cycles=cycles,
                        restarts=restarts, converged=conv, stagnated=stag,
                        rrn=rrn, prev_last=last, nbytes=nbytes,
                        op_reads=op_reads, hist=hist, rst=rst,
                        steps=s["steps"] + steps,
                        spmvs=spmv_head + steps + 1,   # the trips and rrn
                        cycle_len=s["cycle_len"].at[s["cycles"]].set(
                            j_stop, mode="drop"),
                    )
                return run

            def run_cycle(s):
                if n_levels == 1:
                    return run_cycle_at(0)(s)
                return jax.lax.switch(
                    lvl, [run_cycle_at(k) for k in range(n_levels)], s)

            def skip_cycle(s):
                return dict(
                    s, restarts=restarts, converged=jnp.asarray(True),
                    rrn=rr, rst=rst, op_reads=op_head, spmvs=spmv_head,
                )

            return jax.lax.cond(early, skip_cycle, run_cycle, s)

        return jax.lax.while_loop(cond, body, init)

    return solve


def _device_result(state) -> GmresResult:
    """Trim the device state's fixed buffers into the GmresResult contract.

    Everything but ``x`` comes to the host in one fetch and is sliced
    there: a slice on the device by a count would compile a program per
    count."""
    host = jax.device_get({k: v for k, v in state.items() if k != "x"})
    total = int(host["total"])
    restarts = int(host["restarts"])
    return GmresResult(
        x=state["x"],
        rrn=float(host["rrn"]),
        iterations=total,
        converged=bool(host["converged"]),
        rrn_history=np.asarray(host["hist"][:total]),
        restart_rrns=np.asarray(host["rst"][:restarts]),
        restarts=restarts,
        bytes_read=float(host["nbytes"]),
        stagnated=bool(host["stagnated"]),
        op_reads=float(host["op_reads"]),
        steps=int(host["steps"]),
        spmvs=int(host["spmvs"]),
        cycle_lengths=np.asarray(host["cycle_len"][:int(host["cycles"])]),
    )


# ---------------------------------------------------------------------------
# Compiled-solve cache
# ---------------------------------------------------------------------------

# Repeated solves of the same (operator, pipeline, geometry) reuse the jitted
# while_loop program instead of retracing.  Operators are keyed by *content*
# fingerprint (CSR/ELL expose .fingerprint()), so rebuilding the same problem
# — e.g. repeated solve_suite runs — hits the cache instead of growing it;
# bare callables fall back to identity keying, with the callable pinned by
# the entry so its id() stays valid.
_SOLVE_CACHE: OrderedDict = OrderedDict()
_SOLVE_CACHE_SIZE = 16

# jitted cycle/update kernels for the *host*-looped drivers, shared by the
# scalar (_gmres_host) and block (_gmres_block_host) parity oracles.  The
# seed drivers re-jitted these every solve, so repeated solves of the same
# problem recompiled from scratch — the retrace class the trace audit
# (python -m repro.analysis --check) now gates.
_HOST_KERNEL_CACHE: OrderedDict = OrderedDict()
_HOST_KERNEL_CACHE_SIZE = 32


def _operator_key(A, user_matvec, plan=None):
    """Content-based key for the operator, plus any objects to pin.

    A plan (``repro.sparse.plan.OperatorPlan``) supplies the key directly
    when it carries a content fingerprint — its ``key`` already folds in
    the executed reorder and matvec mode, so solves of the same matrix
    under different plans compile separately and repeated solves under
    the same plan share.
    """
    if user_matvec is not None:
        return ("matvec", id(user_matvec)), (user_matvec,)
    if plan is not None and plan.key[0] is not None:
        return ("plan", plan.key), ()
    fp = getattr(A, "fingerprint", None)
    if fp is not None:
        return ("op", fp()), ()
    return ("obj", id(A)), (A,)


def _solve_key(op, pipe: _Pipeline, fields: tuple):
    """The one compiled-solve cache key, and the objects its entry pins.

    Every value a cached build closes over is in it: the operator's key
    (:func:`_operator_key` of ``op = (A, user_matvec, plan)``), the
    resolved pipeline, and ``fields``, what differs per program (driver,
    batching, iteration limits, shards, transport, axis).  The pins keep
    alive what is keyed by ``id()``: a user's callable, the
    preconditioner (whose ``spec()`` may key on ``id(fn)``).
    """
    op_key, pins = _operator_key(*op)
    acc = pipe.accs[0]
    key = (op_key, pipe.policy.spec(), pipe.ortho.spec(), pipe.precond.spec(),
           pipe.dist.spec(), acc.m, acc.n, getattr(acc, "p", 0),
           jnp.dtype(acc.arith_dtype).name) + fields
    return key, pins + (pipe.precond,)


def _cached(cache: OrderedDict, maxsize: int, op, pipe: _Pipeline,
            fields: tuple, build):
    """Bounded-LRU memoization shared by the solve caches, keyed by
    :func:`_solve_key`.

    ``build()`` returns the entry's payload, a tuple; the entry is that
    payload and the key's pins.  An unhashable key (a custom policy or
    preconditioner spec) builds the entry uncached.
    """
    key, pins = _solve_key(op, pipe, fields)
    try:
        hash(key)
    except TypeError:
        return build() + (pins,)
    ent = cache.get(key)
    if ent is not None:
        cache.move_to_end(key)
        return ent
    ent = cache[key] = build() + (pins,)
    while len(cache) > maxsize:
        cache.popitem(last=False)
    return ent


def _cached_solve(op, pipe: _Pipeline, batched: bool, m, max_iters, eta,
                  target):
    """The compiled solve ``(b, x0, operand) -> state`` of this problem
    (cached), and the ``operand`` to call it with (:func:`_operand`)."""
    accs, policy, ortho, precond, _, matvec = pipe
    operand = _operand(op[1], matvec)
    closed = operand is None

    def build():
        def solve(b, x0, op):
            return _device_solve_fn(matvec if closed else op, accs, policy,
                                    m, max_iters, eta, target, ortho,
                                    precond)(b, x0)

        return (jax.jit(jax.vmap(solve, in_axes=(0, 0, None)) if batched
                        else solve),)

    solve = _cached(_SOLVE_CACHE, _SOLVE_CACHE_SIZE, op, pipe,
                    ("device", batched, m, max_iters, float(eta),
                     float(target)), build)[0]
    return solve, operand


def _operand(user_matvec, matvec):
    """The operator argument of a compiled solve ``(b, x0, operand)``:
    ``operator_matvec``'s pytree where its leaves are arrays, so that XLA
    cannot specialise the program to the operator's values (as constants,
    a diagonal of equal values folds to a scalar, and the SpMV then reads
    fewer bytes than the operator holds); ``None`` where the program
    closes over ``matvec``: a user's callable, or an operator that is not
    a pytree of arrays."""
    if user_matvec is None and all(isinstance(leaf, jax.Array)
                                   for leaf in jax.tree.leaves(matvec)):
        return matvec
    return None


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def gmres(
    A: Any,
    b: jax.Array,
    *,
    x0: jax.Array | None = None,
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
    shard: int | None = None,
    shard_transport: str = "plain",
    shard_matvec: str = "auto",
    shard_grid: Any = None,
    reorder: str = "auto",
) -> GmresResult:
    """Solve A x = b with restarted (CB-)GMRES.

    ``A`` is anything with ``.matvec`` (CSR/ELL) unless ``matvec`` is given.
    ``storage`` is a storage format object (any
    :class:`~repro.core.accessor.StorageFormat`) or a format name
    ('float64', 'float32', 'frsz2_32', 'mixed:2:frsz2_32', ...).  Default:
    the arithmetic dtype (classic uncompressed GMRES).

    Pipeline arguments (see :mod:`repro.solver.pipeline`):

    ``policy`` selects the storage format *per restart cycle*: a
    :class:`~repro.solver.pipeline.PrecisionPolicy` or a name
    (``'adaptive'``, ``'adaptive:auto'`` — switch points derived from
    ``target_rrn`` and the format epsilons,
    ``'adaptive:float64,frsz2_32@1e-2,frsz2_16@1e-6'``,
    ``'static:frsz2_32'``).  Overrides ``storage`` when given.
    ``precond`` is applied as right preconditioning inside the jitted
    cycle: ``'jacobi'``, a callable ``x -> M^{-1} x``, or a
    :class:`~repro.solver.pipeline.Preconditioner`.
    ``ortho`` picks the orthogonalization: ``'mgs'`` (seed scheme) or
    ``'cgs2'``.

    ``driver`` selects the restart loop: ``"device"`` (default) runs the
    whole solve as one jitted ``lax.while_loop``; ``"host"`` is the
    python-looped driver with one device sync per cycle (kept for parity
    testing and driver-overhead measurement).

    ``shard`` runs the entire device-resident solve inside ``jax.shard_map``
    over that many devices: basis rows, ``b``, ``x``, and the operator's
    rows split along the vector dim; norms and dot products reduce over the
    mesh axis (see :mod:`repro.solver.sharded`).  ``shard_transport``
    selects the collective wire format: ``"plain"`` (exact psum — parity
    with the single-device solve), ``"compressed"`` (the partial dot
    products travel as FRSZ2 codes), or ``"compressed+norms"`` (norm
    reductions compressed too — more wire bytes for a scalar, measured by
    ``benchmarks/shard_wire.py``; exists for apples-to-apples accounting).
    ``shard_matvec`` picks the row-partitioned SpMV: ``"auto"`` (probe the
    operator's bandwidth — neighbor halo exchange for banded operators,
    gathered operand otherwise; the 3-D block partition when the operator
    carries cell geometry and its modelled face wire wins), ``"halo"``,
    ``"rows"``, ``"replicated"``, or ``"block3d"`` (see
    :func:`repro.sparse.shard.partition_matvec`).  ``shard_grid`` forces
    the block partition's ``(Px, Py, Pz)`` process-grid factorization.
    ``reorder`` applies an RCM bandwidth-reduction permutation at setup
    (:mod:`repro.sparse.plan`): ``"auto"`` (default) permutes only when it
    unlocks the sharded halo matvec for an otherwise-unstructured
    operator; ``"rcm"`` forces the permutation (the solve runs in
    permuted coordinates; ``b``/``x0`` are mapped in and ``x`` back out
    transparently); ``"none"`` disables it.

    The solve runs inside a ``gmres.solve`` profiler span
    (``jax.profiler.TraceAnnotation``), on the clock of the device's ops.
    """
    with jax.profiler.TraceAnnotation("gmres.solve"):
        if shard is not None:
            if driver != "device":
                raise ValueError("shard= requires the device driver")
            from repro.solver.sharded import sharded_gmres

            return sharded_gmres(
                A, b, x0=x0, storage=storage, policy=policy, precond=precond,
                ortho=ortho, m=m, max_iters=max_iters, target_rrn=target_rrn,
                arith_dtype=arith_dtype, eta=eta, matvec=matvec, shard=shard,
                transport=shard_transport, partition_mode=shard_matvec,
                reorder=reorder, pgrid=shard_grid)
        if driver == "device":
            solve, args, plan = solve_program(
                A, b, x0=x0, storage=storage, policy=policy, precond=precond,
                ortho=ortho, m=m, max_iters=max_iters, target_rrn=target_rrn,
                arith_dtype=arith_dtype, eta=eta, matvec=matvec,
                reorder=reorder)
            res = _device_result(solve(*args))
        elif driver == "host":
            plan, A, b, x0, pipe = _prepare(
                A, b, x0, storage, policy, precond, ortho, m, arith_dtype,
                matvec, target_rrn, reorder)
            res = _gmres_host(pipe, b, x0, m, max_iters, target_rrn, eta,
                              (A, matvec, plan))
        else:
            raise ValueError(f"unknown driver {driver!r}")
        if plan is not None:
            res.x = plan.unpermute(res.x)
        return res


def _prepare(A, b, x0, storage, policy, precond, ortho, m, arith_dtype,
             matvec, target_rrn, reorder, block=False):
    """The one plan step of the unsharded entry points: plan (and permute
    ``b``, ``x0`` and the preconditioner into) the operator's coordinates,
    resolve the pipeline, and cast ``b`` and ``x0`` (zeros by default) to
    the arithmetic dtype.  Returns ``(plan, A, b, x0, pipe)``, ``A`` the
    operator the solve runs on."""
    plan = _plan_unsharded(A, reorder, matvec)
    if plan is not None:
        precond = _permuted_precond(precond, plan)
        A = plan.operator
        b = plan.permute(b)
        if x0 is not None:
            x0 = plan.permute(x0)
    pipe = _resolve(A, b, storage, policy, m, arith_dtype, matvec, precond,
                    ortho, target_rrn, block=block)
    b = b.astype(pipe.accs[0].arith_dtype)
    x0 = jnp.zeros_like(b) if x0 is None else x0.astype(b.dtype)
    return plan, A, b, x0, pipe


def solve_program(A, b, *, x0=None, storage=None, policy=None, precond=None,
                  ortho="mgs", m: int = 100, max_iters: int = 20000,
                  target_rrn: float = 1e-14, arith_dtype=None,
                  eta: float = 0.7071067811865475, matvec=None,
                  reorder: str = "auto"):
    """The program that ``gmres(A, b, ...)`` runs.

    Returns ``(solve, args, plan)``: the cached jitted solve, the
    arguments ``gmres`` calls it with (``b``, ``x0`` and the operator's
    arrays, see :func:`_operand`), and the reordering plan (``None``
    without one).  ``solve.lower(*args).compile()`` is the executable
    ``gmres`` runs, for reading its memory analysis and HLO.

    The layout follows from the input (:func:`repro.solver.layout.
    solve_chips`): a solve that fits one chip's memory is the one-device
    program; one that does not runs on every local chip
    (:func:`repro.solver.sharded.sharded_program`), behind the same
    contract: ``plan`` is ``None``, the state's ``x`` is in the operator's
    order.  Either way a CSR's own arrays then move to the host
    (:meth:`~repro.sparse.csr.CSR.to_host`) where the operand holds the
    operator in another form (its slabs, or its DIA form on one chip), so
    that no device holds the operator twice.

    Host spans: ``gmres.solve_program`` around it all, ``gmres.layout``
    around the choice of chips (and on several, the placement of ``b``),
    ``gmres.plan`` around the plan, the pipeline and the operator's SpMV
    (:func:`~repro.sparse.csr.operator_matvec`), and ``gmres.lookup``
    around the fingerprint and the compiled-solve cache.
    """
    with jax.profiler.TraceAnnotation("gmres.solve_program"):
        with jax.profiler.TraceAnnotation("gmres.layout"):
            chips = _layout(A, b, storage, policy, m, arith_dtype, matvec,
                            target_rrn, reorder)
        if chips > 1:
            from repro.solver.sharded import sharded_program

            solve, args = sharded_program(
                A, b, chips, x0=x0, storage=storage, policy=policy,
                precond=precond, ortho=ortho, m=m, max_iters=max_iters,
                target_rrn=target_rrn, arith_dtype=arith_dtype, eta=eta)
            return solve, args, None
        with jax.profiler.TraceAnnotation("gmres.plan"):
            plan, A, b, x0, pipe = _prepare(
                A, b, x0, storage, policy, precond, ortho, m, arith_dtype,
                matvec, target_rrn, reorder)
        with jax.profiler.TraceAnnotation("gmres.lookup"):
            solve, operand = _cached_solve((A, matvec, plan), pipe, False, m,
                                           max_iters, eta, target_rrn)
        if (operand is not None and isinstance(A, CSR)
                and A.to_dia() is not None):
            A.to_host()          # the operand is its DIA form: free the CSR
        return solve, (b, x0, operand), plan


def _layout(A, b, storage, policy, m, arith_dtype, matvec, target_rrn,
            reorder) -> int:
    """The chips a ``solve_program`` solve runs on: one, unless the
    operator can be split by rows (a CSR, no user ``matvec``, no forced
    reordering) and the one-device solve would not fit a chip."""
    from repro.solver.layout import solve_chips

    if matvec is not None or reorder == "rcm" or not isinstance(A, CSR):
        return 1
    if arith_dtype is None:
        arith_dtype = b.dtype
    formats = resolve_policy(policy, storage, arith_dtype, target_rrn,
                             m).formats()
    return solve_chips(A, A.shape[0], m, formats, arith_dtype)


def gmres_batched(
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
    method: str = "vmap",
    driver: str = "device",
    shard: int | None = None,
    shard_transport: str = "plain",
    shard_matvec: str = "auto",
    shard_grid: Any = None,
    reorder: str = "auto",
) -> list[GmresResult]:
    """Solve A X[i] = B[i] for a batch of right-hand sides ``B (k, n)``.

    ``method`` selects the batching strategy:

    * ``"vmap"`` (default) — p *independent* Krylov spaces: vmaps the
      device-resident driver, one XLA program advances all systems
      together (the while_loop runs until every system has converged or
      hit its iteration budget; finished systems are masked by the
      batching rule).  Operator and basis are read once **per RHS** per
      sweep.
    * ``"block"`` — one *shared* block-Krylov space
      (:func:`repro.solver.block.gmres_block`): each basis row is a block
      of p coupled vectors, so every Arnoldi sweep reads the operator and
      the shared basis **once for the whole batch** — the bandwidth
      amortization measured by ``benchmarks/block_gmres.py``.  Converged
      or linearly-dependent right-hand sides are deflated at restarts.

    The full pipeline (``policy``/``precond``/``ortho``) is supported by
    both methods.  Returns one :class:`GmresResult` per right-hand side.
    ``driver`` is ``"device"`` (one jitted while_loop) or ``"host"`` (the
    python-looped parity oracle) for either method.

    ``shard`` composes multi-device row partitioning with the batch: the
    solve runs as ``shard_map`` over the vector dim with the batch loop
    *inside* (vmap over RHS, or the block cycle over block vectors
    partitioned along ``n`` — one halo exchange serves all p RHS) — one
    XLA program, ``k`` systems, ``shard`` devices.  See :func:`gmres`.
    """
    if B.ndim != 2:
        raise ValueError(f"B must be (batch, n), got {B.shape}")
    if method not in ("vmap", "block"):
        raise ValueError(f"unknown batched method {method!r}; "
                         f"expected one of ('vmap', 'block')")
    if driver not in ("device", "host"):
        raise ValueError(f"unknown driver {driver!r}; "
                         f"expected one of ('device', 'host')")
    if shard is not None:
        if driver != "device":
            raise ValueError("shard= requires the device driver")
        from repro.solver.sharded import sharded_gmres

        return sharded_gmres(
            A, B, batched=True, x0=X0, storage=storage, policy=policy,
            precond=precond, ortho=ortho, m=m, max_iters=max_iters,
            target_rrn=target_rrn, arith_dtype=arith_dtype, eta=eta,
            matvec=matvec, shard=shard, transport=shard_transport,
            partition_mode=shard_matvec, reorder=reorder, method=method,
            pgrid=shard_grid)
    if method == "block":
        from repro.solver.block import gmres_block

        return gmres_block(
            A, B, X0=X0, storage=storage, policy=policy, precond=precond,
            ortho=ortho, m=m, max_iters=max_iters, target_rrn=target_rrn,
            arith_dtype=arith_dtype, eta=eta, matvec=matvec, driver=driver,
            reorder=reorder)
    if driver == "host":
        return [
            gmres(A, B[i], x0=None if X0 is None else X0[i],
                  storage=storage, policy=policy, precond=precond,
                  ortho=ortho, m=m, max_iters=max_iters,
                  target_rrn=target_rrn, arith_dtype=arith_dtype, eta=eta,
                  matvec=matvec, driver="host", reorder=reorder)
            for i in range(B.shape[0])
        ]
    plan, A, B, X0, pipe = _prepare(A, B, X0, storage, policy, precond,
                                    ortho, m, arith_dtype, matvec,
                                    target_rrn, reorder)
    solve, operand = _cached_solve((A, matvec, plan), pipe, True, m,
                                   max_iters, eta, target_rrn)
    states = solve(B, X0, operand)
    k = B.shape[0]
    results = [
        _device_result(jax.tree.map(lambda a: a[i], states)) for i in range(k)
    ]
    if plan is not None:
        for r in results:
            r.x = plan.unpermute(r.x)
    return results


def cb_gmres(A, b, storage="frsz2_32", **kw) -> GmresResult:
    """Compressed-Basis GMRES: GMRES with a non-native storage format."""
    return gmres(A, b, storage=storage, **kw)


def build_device_solve(A, b, *, storage=None, policy=None, precond=None,
                       ortho="mgs", m: int = 30, max_iters: int = 2000,
                       target_rrn: float = 1e-10, arith_dtype=None,
                       eta: float = 0.7071067811865475, matvec=None):
    """Resolve the pipeline and return the un-jitted ``(b, x0) -> state``
    device solve plus its accessors — the introspection surface.

    ``jax.make_jaxpr(solve)(b, x0)`` exposes the whole device-resident
    restart loop (the cycle jaxpr included) for structural audits:
    ``repro.analysis.traceaudit`` walks it for f64 leaks in
    compressed-format policies and checks the
    :func:`repro.dist.sharding.driver_partition_specs` tree against the
    actual ``lax.while_loop`` state via ``jax.eval_shape``.  Semantics are
    identical to ``gmres(..., driver="device")`` minus jit, caching, and
    result trimming.
    """
    pipe = _resolve(A, b, storage, policy, m, arith_dtype, matvec, precond,
                    ortho, target_rrn)
    solve = _device_solve_fn(pipe.matvec, pipe.accs, pipe.policy, m,
                             max_iters, eta, target_rrn, pipe.ortho,
                             pipe.precond)
    return solve, pipe.accs
