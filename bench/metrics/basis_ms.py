"""Device time per GMRES iteration, in ms, of the operations that read or
write the Krylov basis: dot products, combines, compression, and the
store's writes, copies and relayouts."""


def read(ctx):
    basis_s, its = ctx.layer_s.get("basis", 0.0), sum(ctx.iterations)
    if basis_s <= 0 or its <= 0:
        return None
    return 1e3 * basis_s / its
