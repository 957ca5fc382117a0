"""Device time per solve, in chip-ms, of the basis operations under none of
the program's named scopes: the copies and relayouts of the Krylov store
that XLA adds around the loop, outside the accessor's ``dots``,
``combine``, ``store``, ``compress`` and ``decode``."""


def read(ctx):
    copy_s = ctx.scope_s.get(("basis", ""), 0.0)
    if copy_s <= 0 or not ctx.iterations:
        return None
    return 1e3 * copy_s / len(ctx.iterations)
