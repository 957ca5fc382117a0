"""Composable GMRES cycle pipeline: the three pluggable stages.

The seed solver hard-wired one orthogonalization scheme, no preconditioning,
and a storage format frozen for the whole solve.  This module factors those
three decisions out of ``repro.solver.gmres`` into small protocol objects so
they compose freely (Loe et al., arXiv:2105.07544 / arXiv:2109.01232: the
biggest multiprecision-GMRES wins come from *varying* precision and
preconditioning across the solve):

  * :class:`Orthogonalizer` — how ``w`` is orthogonalized against the basis
    each Arnoldi step.  ``mgs`` is the seed scheme (one-shot dots/combine
    plus the conditional "twice is enough" re-orthogonalization, paper
    Fig. 1 steps 6-10); ``cgs2`` always runs two batched passes through the
    fused :meth:`StorageFormat.dots` path — twice the basis traffic, but
    unconditionally orthogonal to machine precision and free of the
    data-dependent branch.
  * :class:`Preconditioner` — right preconditioning ``A M^{-1}``: the
    Arnoldi matvec becomes ``A (M^{-1} v)`` and the solution update becomes
    ``x += M^{-1} (V y)``, so the explicit restart residual ``b - A x`` is
    the *true* residual (no preconditioned-norm bookkeeping).  Identity,
    Jacobi (``M = diag(A)``), and a user-callable hook.  All applications
    happen inside the jitted cycle of both drivers.
  * :class:`PrecisionPolicy` — which storage format holds the Krylov basis,
    chosen *per restart cycle* from the explicit restart residual.
    :class:`StaticPolicy` freezes one format (the seed behaviour);
    :class:`AdaptivePolicy` drops precision as the residual falls (inexact
    Krylov: the further the solve has progressed, the more basis error it
    tolerates), e.g. ``float64 -> frsz2_32 -> frsz2_16``.  The device
    driver pre-builds one store per level and dispatches with
    ``lax.switch`` so the whole solve stays one XLA program.

Every object is stateless-or-frozen and exposes a hashable ``spec()`` used
by the compiled-solve cache, so pipelines key cleanly alongside the
operator fingerprint.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accessor import HIGHEST, StorageFormat, format_by_name
from repro.dist.context import LOCAL

__all__ = [
    "Orthogonalizer",
    "MGSOrthogonalizer",
    "CGS2Orthogonalizer",
    "orthogonalizer_by_name",
    "BlockOrthogonalizer",
    "BlockMGSOrthogonalizer",
    "BlockCGS2Orthogonalizer",
    "block_orthogonalizer_by_name",
    "block_qr",
    "Preconditioner",
    "IdentityPreconditioner",
    "JacobiPreconditioner",
    "CallablePreconditioner",
    "resolve_preconditioner",
    "PrecisionPolicy",
    "StaticPolicy",
    "AdaptivePolicy",
    "policy_by_name",
    "resolve_policy",
]


# ---------------------------------------------------------------------------
# Orthogonalizers
# ---------------------------------------------------------------------------


class Orthogonalizer:
    """Orthogonalize ``w`` against the masked rows of the basis.

    ``__call__(acc, store, w, mask, eta, dist, w_norm) -> (w_orth, h, hj1,
    fired)`` where ``h`` is the Hessenberg column against the masked rows,
    ``hj1 = ||w_orth||``, and ``fired`` is an int32 flag for an *extra*
    basis sweep beyond the nominal ``passes`` this iteration actually ran
    (MGS's conditional re-orthogonalization) — the drivers fold it into
    the ``bytes_read`` traffic accounting.

    ``dist`` is a :class:`~repro.dist.context.DistContext`: all vector
    norms go through ``dist.norm`` so the same orthogonalizer runs on full
    vectors (single device) and on row-partitioned chunks inside
    ``shard_map`` (norms become psum-of-local-squares).  ``w_norm`` is the
    caller's already-reduced ``||w||`` (the cycle computes it for the
    breakdown check); passing it through avoids a second scalar psum per
    iteration in sharded solves.  ``passes`` is the nominal number of full
    basis sweeps per iteration.
    """

    name: str = "base"
    passes: int = 1

    def __call__(self, acc, store, w, mask, eta, dist=LOCAL,
                 w_norm=None):  # pragma: no cover
        raise NotImplementedError

    def spec(self):
        return ("ortho", self.name)


class MGSOrthogonalizer(Orthogonalizer):
    """Seed scheme: one-shot dots/combine + conditional re-orthogonalization.

    Re-orthogonalizes iff ``||w_orth|| < eta * ||w||`` (Fig. 1 steps 6-10,
    the "twice is enough" criterion) — bit-identical to the seed solver.
    """

    name = "mgs"
    passes = 1

    def __call__(self, acc, store, w, mask, eta, dist=LOCAL, w_norm=None):
        w_pre = dist.norm(w) if w_norm is None else w_norm
        h = acc.dots(store, w, mask)
        w = w - acc.combine(store, h, mask)
        hj1 = dist.norm(w)
        fired = hj1 < eta * w_pre

        def reorth(args):
            w, h, _ = args
            u = acc.dots(store, w, mask)
            w2 = w - acc.combine(store, u, mask)
            return w2, h + u, dist.norm(w2)

        w, h, hj1 = jax.lax.cond(fired, reorth, lambda a: a, (w, h, hj1))
        return w, h, hj1, fired.astype(jnp.int32)


class CGS2Orthogonalizer(Orthogonalizer):
    """Classical Gram-Schmidt, applied twice unconditionally (CGS-2).

    Both passes batch all dot products through the fused
    :meth:`StorageFormat.dots` path — two dense basis sweeps, no
    data-dependent branch.  Orthogonality is machine-precision regardless
    of how ill-conditioned the new direction is.
    """

    name = "cgs2"
    passes = 2

    def __call__(self, acc, store, w, mask, eta, dist=LOCAL, w_norm=None):
        h = acc.dots(store, w, mask)
        w = w - acc.combine(store, h, mask)
        u = acc.dots(store, w, mask)
        w = w - acc.combine(store, u, mask)
        # both sweeps are already in the nominal `passes`: no extras
        return w, h + u, dist.norm(w), jnp.asarray(0, jnp.int32)


_ORTHOGONALIZERS = {"mgs": MGSOrthogonalizer, "cgs2": CGS2Orthogonalizer}


def orthogonalizer_by_name(name) -> Orthogonalizer:
    if isinstance(name, Orthogonalizer):
        return name
    try:
        return _ORTHOGONALIZERS[name]()
    except KeyError:
        raise ValueError(
            f"unknown orthogonalizer {name!r}; "
            f"have {sorted(_ORTHOGONALIZERS)}") from None


# ---------------------------------------------------------------------------
# Block orthogonalizers (block-GMRES: one basis sweep serves all p RHS)
# ---------------------------------------------------------------------------

_TINY = 1e-300
#: relative threshold below which a new block direction is declared linearly
#: dependent and deflated (its q column zeroed, its T diagonal zeroed) —
#: relative to the largest column scale of the incoming block, so converged
#: RHS columns (exactly zero residual blocks) always deflate.
DEFLATE_RTOL = 1e-13


def block_qr(W, dist=LOCAL, scale=None):
    """Rank-revealing QR of a block ``W (p, n)`` of row-stacked vectors.

    Returns ``(Q, T, dep)`` with ``W[b] = sum_{a<=b} T[a, b] Q[a]``:
    ``Q (p, n)`` has orthonormal rows except where ``dep`` marks a column
    as linearly dependent (or zero) — those rows are exact zeros and their
    ``T`` diagonal is 0.  This is the deflation mechanism of block-GMRES:
    converged or dependent right-hand sides stop contributing basis
    directions but keep their (upper-triangular) couplings, so the block
    Arnoldi relation stays exact.

    Gram-Schmidt with a second projection pass (CGS2-strength within the
    block; ``p`` is small, the columns loop is static).  All inner products
    route through ``dist`` so the same QR runs on full vectors and on
    row-partitioned chunks inside ``shard_map`` — one batched ``(k,)``
    reduction per column, not ``k`` scalar ones.
    """
    p = W.shape[0]
    ad = W.dtype
    if scale is None:
        scale = dist.col_norms(W)
    block_scale = jnp.max(scale)
    Q = jnp.zeros_like(W)
    T = jnp.zeros((p, p), ad)
    dep = jnp.zeros((p,), bool)
    for k in range(p):
        wk = W[k]
        if k:
            r = dist.sum(jnp.matmul(Q[:k], wk, precision=HIGHEST))
            wk = wk - jnp.matmul(r, Q[:k], precision=HIGHEST)
            r2 = dist.sum(jnp.matmul(Q[:k], wk, precision=HIGHEST))
            wk = wk - jnp.matmul(r2, Q[:k], precision=HIGHEST)
            T = T.at[:k, k].set(r + r2)
        nrm = dist.norm(wk)
        dep_k = nrm <= DEFLATE_RTOL * block_scale + _TINY
        qk = jnp.where(dep_k, 0.0, wk / jnp.maximum(nrm, _TINY))
        Q = Q.at[k].set(qk)
        T = T.at[k, k].set(jnp.where(dep_k, 0.0, nrm))
        dep = dep.at[k].set(dep_k)
    return Q, T, dep


class BlockOrthogonalizer:
    """Orthogonalize a block ``W (p, n)`` against the masked block basis.

    ``__call__(acc, store, W, mask, eta, dist, w_norms) -> (Q, H, T,
    fired)`` where ``acc`` is a
    :class:`~repro.core.accessor.BlockBasisAccessor`, ``H (m+1, p, p)`` are
    the block Hessenberg couplings against the masked rows (one einsum per
    sweep — the whole shared basis is read once for all ``p`` RHS, which is
    the bandwidth amortization this mode exists for), and ``(Q, T)`` is the
    rank-revealing QR of the orthogonalized block (:func:`block_qr` —
    deflated columns have zero ``Q`` rows and zero ``T`` diagonal).

    ``fired`` counts extra conditional sweeps exactly like the scalar
    protocol, and ``w_norms`` is the caller's already-reduced per-column
    norm of ``W`` (saves a reduction, as in the scalar contract).
    """

    name: str = "base"
    passes: int = 1

    def __call__(self, acc, store, W, mask, eta, dist=LOCAL,
                 w_norms=None):  # pragma: no cover
        raise NotImplementedError

    def spec(self):
        return ("block-ortho", self.name)


class BlockMGSOrthogonalizer(BlockOrthogonalizer):
    """Block analogue of the seed scheme: one sweep + conditional reorth.

    The re-orthogonalization fires when *any* column lost more than the
    ``eta`` fraction of its norm — the block shares one basis sweep, so the
    conditional pass is all-or-nothing (a per-column pass would read the
    basis again anyway).
    """

    name = "mgs"
    passes = 1

    def __call__(self, acc, store, W, mask, eta, dist=LOCAL, w_norms=None):
        w_pre = dist.col_norms(W) if w_norms is None else w_norms
        H = acc.block_dots(store, W, mask)
        W = W - acc.block_combine(store, H, mask)
        nrm = dist.col_norms(W)
        fired = jnp.any(nrm < eta * w_pre)

        def reorth(args):
            W, H = args
            U = acc.block_dots(store, W, mask)
            return W - acc.block_combine(store, U, mask), H + U

        W, H = jax.lax.cond(fired, reorth, lambda a: a, (W, H))
        Q, T, _ = block_qr(W, dist, scale=w_pre)
        return Q, H, T, fired.astype(jnp.int32)


class BlockCGS2Orthogonalizer(BlockOrthogonalizer):
    """Two unconditional block sweeps (CGS-2): branch-free, machine-precision
    orthogonality, twice the basis traffic — the same trade as the scalar
    ``cgs2``."""

    name = "cgs2"
    passes = 2

    def __call__(self, acc, store, W, mask, eta, dist=LOCAL, w_norms=None):
        w_pre = dist.col_norms(W) if w_norms is None else w_norms
        H = acc.block_dots(store, W, mask)
        W = W - acc.block_combine(store, H, mask)
        U = acc.block_dots(store, W, mask)
        W = W - acc.block_combine(store, U, mask)
        Q, T, _ = block_qr(W, dist, scale=w_pre)
        return Q, H + U, T, jnp.asarray(0, jnp.int32)


_BLOCK_ORTHOGONALIZERS = {"mgs": BlockMGSOrthogonalizer,
                          "cgs2": BlockCGS2Orthogonalizer}


def block_orthogonalizer_by_name(name) -> BlockOrthogonalizer:
    if isinstance(name, BlockOrthogonalizer):
        return name
    if isinstance(name, Orthogonalizer):
        name = name.name                 # scalar choice carries over by name
    try:
        return _BLOCK_ORTHOGONALIZERS[name]()
    except KeyError:
        raise ValueError(
            f"unknown block orthogonalizer {name!r}; "
            f"have {sorted(_BLOCK_ORTHOGONALIZERS)}") from None


# ---------------------------------------------------------------------------
# Preconditioners (right preconditioning: A M^{-1})
# ---------------------------------------------------------------------------


class Preconditioner:
    """``apply(x) -> M^{-1} x``; applied inside the jitted cycle."""

    def apply(self, x):  # pragma: no cover - overridden
        raise NotImplementedError

    def spec(self):  # pragma: no cover - overridden
        raise NotImplementedError

    def permuted(self, perm) -> Preconditioner:
        """Equivalent preconditioner in RCM-permuted coordinates.

        When an :class:`~repro.sparse.plan.OperatorPlan` reorders the
        operator (``P A Pᵀ``), a preconditioner built for the *original*
        coordinates must be conjugated the same way (``P M⁻¹ Pᵀ``).
        Name-resolved preconditioners never hit this (they are built from
        the already-reordered operator); only user-passed instances with
        positional state do.  ``perm`` maps new indices to old
        (``perm[new] = old``).
        """
        raise NotImplementedError(
            f"{type(self).__name__} cannot be permuted into reordered "
            "coordinates; build it for the reordered operator (see "
            "repro.sparse.plan) or pass reorder='none'")

    def shard_local(self, axis_name: str, n_local: int,
                    n_pad: int | None = None) -> Preconditioner:
        """Equivalent preconditioner over the device-local vector chunk.

        Called once by the sharded driver before it wraps the solve in
        ``shard_map``: ``apply`` will then receive ``(n_local,)`` chunks of
        the row-partitioned vectors.  Formats that hold full-length state
        (Jacobi's diagonal) return a view that slices by
        ``jax.lax.axis_index``; elementwise-stateless ones return ``self``.
        ``n_pad`` is the zero-padded vector length when the problem dim
        does not divide the mesh (state vectors must be identity-extended
        so padded chunk entries stay exact zeros).
        """
        raise NotImplementedError(
            f"{type(self).__name__} does not support sharded application; "
            "implement shard_local() to run it under gmres(..., shard=...)")


class IdentityPreconditioner(Preconditioner):
    """No-op: ``apply`` returns its input unchanged (exact seed parity)."""

    def apply(self, x):
        return x

    def spec(self):
        return ("identity",)

    def shard_local(self, axis_name, n_local, n_pad=None):
        return self

    def permuted(self, perm):
        return self


class JacobiPreconditioner(Preconditioner):
    """Diagonal scaling ``M = diag(A)`` — the classic fix for row-scaled
    (variable-coefficient) systems, where it collapses the artificial
    spread ``D A0`` back to the underlying operator's spectrum."""

    def __init__(self, diag: jax.Array):
        d = jnp.asarray(diag)
        self.inv_diag = jnp.where(d != 0, 1.0 / jnp.where(d != 0, d, 1.0), 1.0)
        self._digest = hashlib.sha1(
            np.asarray(self.inv_diag).tobytes()).hexdigest()

    @classmethod
    def from_operator(cls, A) -> JacobiPreconditioner:
        diag_fn = getattr(A, "diag", None)
        if diag_fn is None:
            raise ValueError(
                "precond='jacobi' needs an operator with .diag() "
                f"(got {type(A).__name__}); pass a Preconditioner instead")
        return cls(diag_fn())

    def apply(self, x):
        return x * self.inv_diag.astype(x.dtype)

    def spec(self):
        return ("jacobi", self._digest)

    def permuted(self, perm):
        perm = np.asarray(perm)
        inv_diag = self.inv_diag
        if perm.shape[0] > inv_diag.shape[0]:
            # padded-space permutation (block3d layout): pad slots map to
            # ids >= n — identity-extend so padded entries stay exact zeros
            inv_diag = jnp.pad(inv_diag,
                               (0, perm.shape[0] - inv_diag.shape[0]),
                               constant_values=1.0)
        new = object.__new__(JacobiPreconditioner)
        new.inv_diag = inv_diag[jnp.asarray(perm)]
        new._digest = hashlib.sha1(
            np.asarray(new.inv_diag).tobytes()).hexdigest()
        return new

    def shard_local(self, axis_name, n_local, n_pad=None):
        inv_diag = self.inv_diag
        if n_pad is not None and n_pad > inv_diag.shape[0]:
            # identity-extend: padded vector entries are exact zeros, and
            # 1.0 * 0 keeps them so (a zero pad would make them 0/0 NaNs)
            inv_diag = jnp.pad(inv_diag,
                               (0, n_pad - inv_diag.shape[0]),
                               constant_values=1.0)
        return _LocalJacobiPreconditioner(
            inv_diag, axis_name, n_local, self._digest)


class _LocalJacobiPreconditioner(Preconditioner):
    """Jacobi over the device-local chunk inside ``shard_map``.

    Holds the *full* inverse diagonal (replicated — it is one vector, not
    the basis) and slices this device's chunk by ``axis_index`` at trace
    time, so ``apply`` maps ``(n_local,) -> (n_local,)``.
    """

    def __init__(self, inv_diag, axis_name: str, n_local: int, digest: str):
        self.inv_diag = inv_diag
        self.axis_name = axis_name
        self.n_local = n_local
        self._digest = digest

    def apply(self, x):
        i = jax.lax.axis_index(self.axis_name)
        d = jax.lax.dynamic_slice_in_dim(
            self.inv_diag, i * self.n_local, self.n_local)
        return x * d.astype(x.dtype)

    def spec(self):
        return ("jacobi-local", self._digest, self.axis_name, self.n_local)

    def shard_local(self, axis_name, n_local, n_pad=None):
        if axis_name != self.axis_name or n_local != self.n_local:
            raise ValueError("preconditioner already sharded differently")
        return self


class CallablePreconditioner(Preconditioner):
    """User hook: any jit-traceable ``fn(x) -> M^{-1} x``.

    Cache identity is the function object (``name`` overrides for closures
    rebuilt per call — give equal hooks the same name to share compiles).
    """

    def __init__(self, fn: Callable, name: str | None = None):
        self.fn = fn
        self.name = name

    def apply(self, x):
        return self.fn(x)

    def spec(self):
        return ("callable", self.name if self.name is not None else id(self.fn))

    def shard_local(self, axis_name, n_local, n_pad=None):
        # The hook will see (n_local,) chunks of row-partitioned vectors.
        # Elementwise hooks are automatically correct only when their state
        # is chunk-shaped; anything holding full-length arrays must be
        # written shard-aware by the caller.
        return self


def resolve_preconditioner(precond, A) -> Preconditioner:
    """None | 'identity' | 'jacobi' | callable | Preconditioner -> object."""
    if precond is None or precond == "identity":
        return IdentityPreconditioner()
    if isinstance(precond, Preconditioner):
        return precond
    if precond == "jacobi":
        return JacobiPreconditioner.from_operator(A)
    if callable(precond):
        return CallablePreconditioner(precond)
    raise ValueError(f"unknown preconditioner {precond!r}")


# ---------------------------------------------------------------------------
# Precision policies
# ---------------------------------------------------------------------------


class PrecisionPolicy:
    """Selects the basis storage format per restart cycle.

    ``formats()`` returns the static tuple of candidate formats (one store
    per format is pre-built by the device driver); ``level(rr, cycle)``
    maps the explicit restart residual (traced or concrete) to an index
    into that tuple.
    """

    def formats(self) -> tuple:  # pragma: no cover - overridden
        raise NotImplementedError

    def level(self, rr, cycle):  # pragma: no cover - overridden
        raise NotImplementedError

    def spec(self):  # pragma: no cover - overridden
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class StaticPolicy(PrecisionPolicy):
    """One format for the whole solve (the seed behaviour)."""

    fmt: StorageFormat

    def formats(self) -> tuple:
        return (self.fmt,)

    def level(self, rr, cycle):
        return jnp.asarray(0, jnp.int32)

    def spec(self):
        return ("static", self.fmt)


@dataclasses.dataclass(frozen=True)
class AdaptivePolicy(PrecisionPolicy):
    """Drop precision as the residual falls (inexact-Krylov schedule).

    ``levels[i]`` is active while ``thresholds[i-1] >= rr > thresholds[i]``
    (``thresholds`` strictly decreasing, one fewer than ``levels``).  The
    level is monotone in ``-log rr``: early cycles run the expensive
    high-precision format, late cycles the cheapest — total basis read
    traffic drops below the uniform mid-precision baseline while the final
    explicit residual (always recomputed in ``arith_dtype``) matches it.
    """

    levels: tuple
    thresholds: tuple

    def __post_init__(self):
        if len(self.thresholds) != len(self.levels) - 1:
            raise ValueError("need len(thresholds) == len(levels) - 1")
        if not all(a > b for a, b in zip(self.thresholds,
                                         self.thresholds[1:])):
            raise ValueError("thresholds must be strictly decreasing")

    def formats(self) -> tuple:
        return tuple(self.levels)

    def level(self, rr, cycle):
        lvl = sum((rr < t).astype(jnp.int32) if hasattr(rr, "astype")
                  else int(rr < t) for t in self.thresholds)
        return jnp.asarray(lvl, jnp.int32)

    def spec(self):
        return ("adaptive", tuple(self.levels), tuple(self.thresholds))

    @classmethod
    def from_target(cls, levels, target_rrn: float,
                    safety: float = 0.5) -> AdaptivePolicy:
        """Derive the switch points from the target RRN and format epsilons.

        Inexact-Krylov accounting: a cycle entered at restart residual
        ``rr`` computes a correction of magnitude ``~rr``, so a basis
        stored with relative error ``eps`` (:meth:`StorageFormat.eps`)
        perturbs the final residual by ``~eps * rr``.  Level ``i`` is
        therefore admissible once ``eps_i * rr <= safety * target_rrn``,
        i.e. below the threshold ``safety * target_rrn / eps_i`` — the
        tighter the target, the longer the solve stays in high precision,
        with no constants to tune per problem.  Thresholds are clipped
        into ``(0, 1]`` and kept strictly decreasing.
        """
        if target_rrn <= 0:
            raise ValueError(f"target_rrn must be positive, "
                             f"got {target_rrn}")
        thresholds = []
        ceiling = 1.0
        for fmt in levels[1:]:
            t = min(safety * float(target_rrn) / fmt.eps(), ceiling)
            # a later (cheaper) level must activate strictly later
            if thresholds and t >= thresholds[-1]:
                t = thresholds[-1] / 2.0
            thresholds.append(t)
            ceiling = t
        return cls(levels=tuple(levels), thresholds=tuple(thresholds))


#: default adaptive ladder: full precision until the residual clears 1e-2,
#: frsz2_32 to 1e-6, frsz2_16 for the long tail — most cycles run at the
#: cheapest level, which is what makes total read traffic beat a uniform
#: frsz2_32 basis.
_ADAPTIVE_DEFAULT = (("float64", None), ("frsz2_32", 1e-2), ("frsz2_16", 1e-6))


def policy_by_name(name: str, *, arith_dtype=jnp.float64,
                   target_rrn: float | None = None,
                   m: int | None = None, **ctx
                   ) -> PrecisionPolicy:
    """Resolve a policy from a name.

    ``static:<fmt>`` — :class:`StaticPolicy` over any registered format.
    ``adaptive`` — the default ``float64 -> frsz2_32@1e-2 -> frsz2_16@1e-6``.
    ``adaptive:auto`` — the same level ladder with switch points *derived*
    from ``target_rrn`` and the format epsilons
    (:meth:`AdaptivePolicy.from_target`); without a target it falls back
    to the fixed default thresholds.
    ``adaptive:<f0>,<f1>@<t1>,<f2>@<t2>,...`` — explicit ladder: the first
    format has no threshold; each later ``fmt@thr`` activates once the
    restart residual falls below ``thr``.

    ``target_rrn`` and ``m`` are threaded through by the solvers (their
    ``target_rrn`` / restart-length arguments); ``adaptive:auto`` and the
    ``mixed:auto:<tail>`` format consume them.
    """
    ctx = dict(ctx, target_rrn=target_rrn, m=m)
    kind, _, rest = name.partition(":")
    if kind == "static":
        if not rest:
            raise ValueError("static policy needs a format: 'static:<fmt>'")
        return StaticPolicy(format_by_name(rest, arith_dtype=arith_dtype,
                                           **ctx))
    if kind != "adaptive":
        raise ValueError(
            f"unknown policy {name!r}; expected one of 'static:<fmt>', "
            f"'adaptive', 'adaptive:auto', or "
            f"'adaptive:<f0>,<f1>@<t1>,...'")
    if rest == "auto":
        if target_rrn is not None:
            levels = tuple(
                format_by_name(f, arith_dtype=arith_dtype, **ctx)
                for f, _ in _ADAPTIVE_DEFAULT)
            return AdaptivePolicy.from_target(levels, target_rrn)
        ladder = _ADAPTIVE_DEFAULT       # no target: the fixed defaults
    elif not rest:
        ladder = _ADAPTIVE_DEFAULT
    else:
        ladder = []
        for i, part in enumerate(rest.split(",")):
            fmt_name, _, thr = part.partition("@")
            if i == 0 and not thr:
                ladder.append((fmt_name, None))
            elif not thr:
                raise ValueError(
                    f"adaptive level {part!r} needs a threshold 'fmt@thr'")
            else:
                ladder.append((fmt_name, float(thr)))
    levels = tuple(format_by_name(f, arith_dtype=arith_dtype, **ctx)
                   for f, _ in ladder)
    thresholds = tuple(t for _, t in ladder[1:])
    return AdaptivePolicy(levels=levels, thresholds=thresholds)


def resolve_policy(policy, storage, arith_dtype,
                   target_rrn: float | None = None,
                   m: int | None = None) -> PrecisionPolicy:
    """Combine the ``policy`` / ``storage`` arguments into one policy.

    ``policy`` wins when given (object or name); otherwise the storage
    format (object, name, or None -> native arith dtype) becomes a
    :class:`StaticPolicy` — the seed code path, bit for bit.
    ``target_rrn`` feeds ``adaptive:auto``'s derived thresholds; together
    with ``m`` it also sizes ``mixed:auto:<tail>`` heads.
    """
    from repro.core.accessor import NativeFormat

    if policy is not None:
        if isinstance(policy, PrecisionPolicy):
            return policy
        if isinstance(policy, str):
            return policy_by_name(policy, arith_dtype=arith_dtype,
                                  target_rrn=target_rrn, m=m)
        raise ValueError(
            f"unknown policy {policy!r}; expected a PrecisionPolicy or a "
            f"name ('static:<fmt>', 'adaptive', 'adaptive:auto', "
            f"'adaptive:<f0>,<f1>@<t1>,...')")
    if storage is None:
        return StaticPolicy(NativeFormat(dtype=arith_dtype))
    if isinstance(storage, str):
        return StaticPolicy(format_by_name(storage, arith_dtype=arith_dtype,
                                           target_rrn=target_rrn, m=m))
    if isinstance(storage, PrecisionPolicy):
        return storage
    return StaticPolicy(storage)
