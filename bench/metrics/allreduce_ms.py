"""Device time per solve, in chip-ms, of the sharded solve's all-reduces:
every operation whose innermost named scope is ``allreduce``
(``dist/collectives.py`` ``psum``, ``compressed_psum``), whatever its
layer: the dot products' in the basis, the norms' in the reductions and
the driver.  A program on one chip reduces over no chips, and the reader
then returns ``None``."""


def read(ctx):
    reduce_s = sum(s for (_, scope), s in ctx.scope_s.items()
                   if scope == "allreduce")
    if reduce_s <= 0 or not ctx.iterations:
        return None
    return 1e3 * reduce_s / len(ctx.iterations)
