"""Device time of the SpMV's operations per GMRES iteration, in ms."""


def read(ctx):
    spmv_s, its = ctx.layer_s.get("spmv", 0.0), sum(ctx.iterations)
    if spmv_s <= 0 or its <= 0:
        return None
    return 1e3 * spmv_s / its
