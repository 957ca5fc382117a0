"""Share of the restart driver's inner-loop trips that were iterations:
``100 * iterations / steps`` over the window's solves.  A cycle runs all
``m`` trips, and those past convergence are masked (``GmresResult.steps``
counts them)."""


def read(ctx):
    if ctx.steps is None or sum(ctx.steps) <= 0 or not ctx.iterations:
        return None
    return 100.0 * sum(ctx.iterations) / sum(ctx.steps)
