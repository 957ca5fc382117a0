"""DistContext: the solver's single hook for distributed reductions.

The GMRES drivers are written against one tiny object instead of calling
``jnp.linalg.norm`` directly.  With no axis name bound (the default), every
operation is the plain local computation and the solver is bit-identical to
the unsharded seed code path.  With an axis name bound — i.e. when the whole
driver runs inside ``jax.shard_map`` over row-partitioned vectors — norms
become psum-of-local-squares over the mesh axis, so the same jitted cycle
serves both the single-device and the multi-device solve.

``compressed_norms`` optionally ships the local partial squares as FRSZ2
codes through :func:`repro.dist.collectives.compressed_psum` — the same
wire codec the sharded basis' ``dots`` reduction uses.  Note that for a
*scalar* reduction this always costs more wire bytes than a plain ``psum``
(one FRSZ2 block is 128 codes + an exponent word, a scalar is 8 bytes), so
it is off by default; ``benchmarks/shard_wire.py`` quantifies the
difference.  The knob exists so the whole solve can run with every
collective on the compressed transport for apples-to-apples wire accounting.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.dist import collectives as _collectives

__all__ = ["DistContext", "LOCAL"]


@dataclasses.dataclass(frozen=True)
class DistContext:
    """Where reductions happen: locally, or across a shard_map axis.

    ``axis_name is None`` (default) means the solver owns the full vectors
    and every reduction is local.  Otherwise each vector argument is the
    device-local chunk of a row-partitioned vector and reductions ``psum``
    over ``axis_name``.
    """

    axis_name: str | None = None
    compressed_norms: bool = False

    @property
    def sharded(self) -> bool:
        return self.axis_name is not None

    def sum(self, x):
        """Global sum of an already locally-reduced value (any shape)."""
        if self.axis_name is None:
            return x
        if self.compressed_norms:
            return _collectives.compressed_psum(
                x, self.axis_name).astype(x.dtype)
        return _collectives.psum(x, self.axis_name)

    def norm(self, x):
        """||x|| of the (possibly row-partitioned) vector ``x``."""
        if self.axis_name is None:
            return jnp.linalg.norm(x)
        return jnp.sqrt(self.sum(jnp.sum(jnp.square(x))))

    def col_norms(self, X):
        """Per-column norms of a block ``X (p, n)`` of row-stacked
        (possibly row-partitioned) vectors: ``||X[b]||`` for each b.

        The block-GMRES analogue of :meth:`norm` — one reduction of ``p``
        partial squares instead of ``p`` scalar reductions.
        """
        sq = jnp.sum(jnp.square(X), axis=-1)
        if self.axis_name is None:
            return jnp.sqrt(sq)
        return jnp.sqrt(self.sum(sq))

    def spec(self):
        """Hashable identity for the compiled-solve cache."""
        return ("dist", self.axis_name, self.compressed_norms)


#: the default, single-device context: every reduction is local.
LOCAL = DistContext()
