"""Device time of the SpMV's operations per operator application run, in
chip-ms: the ``spmv`` layer's time over ``GmresResult.spmvs``, masked trips
and the loop head's residuals included."""


def read(ctx):
    spmv_s = ctx.layer_s.get("spmv", 0.0)
    if ctx.spmvs is None or sum(ctx.spmvs) <= 0 or spmv_s <= 0:
        return None
    return 1e3 * spmv_s / sum(ctx.spmvs)
