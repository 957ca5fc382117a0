"""The SpMV's share of its HBM roofline: the least bytes of the SpMVs the
solves needed (``roofline.spmv_bytes`` times ``roofline.spmv_calls``) over
the chip's HBM bandwidth, divided by the device time of the SpMV's
operations."""
import numpy as np

import roofline


def read(ctx):
    width = np.dtype(ctx.config["arithmetic"]).itemsize
    calls = sum(roofline.spmv_calls(c) for c in ctx.cycles)
    least = calls * roofline.spmv_bytes(ctx.n, ctx.nnz, width)
    return roofline.share_pct(least, ctx.layer_s.get("spmv", 0.0),
                              ctx.peak["hbm_bytes_per_s"])
