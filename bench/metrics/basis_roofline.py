"""The basis's share of its HBM roofline: the least basis bytes of the
solves (``roofline.basis_bytes`` over each solve's restart cycles, at the
format's stored bits per value) over the chip's HBM bandwidth, divided by
the basis operations' device time."""
import roofline


def read(ctx):
    bits = roofline.stored_bits(ctx.traffic["storage"])
    least = sum(roofline.basis_bytes(c, ctx.n, bits) for c in ctx.cycles)
    return roofline.share_pct(least, ctx.layer_s.get("basis", 0.0),
                              ctx.peak["hbm_bytes_per_s"])
