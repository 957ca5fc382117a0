"""Device time per solve, in chip-ms, of the sharded SpMV's halo exchange:
the SpMV's operations under the named scope ``halo``
(``dist/collectives.py`` ``halo_exchange``), the ``ppermute``s and the
slicing around them.  A program on one chip exchanges no halo, and the
reader then returns ``None``."""


def read(ctx):
    halo_s = ctx.scope_s.get(("spmv", "halo"), 0.0)
    if halo_s <= 0 or not ctx.iterations:
        return None
    return 1e3 * halo_s / len(ctx.iterations)
