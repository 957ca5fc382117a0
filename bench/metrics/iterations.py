"""Mean iterations per solve, as the restart driver (``solver/gmres.py``)
returns them in ``GmresResult.iterations``."""


def read(ctx):
    if not ctx.iterations:
        return None
    return sum(ctx.iterations) / len(ctx.iterations)
