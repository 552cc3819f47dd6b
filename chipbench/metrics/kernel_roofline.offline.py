"""Roofline share of the device's busy time: the least time one chip could
take for the batches it executed in the traced window (the larger of FLOPs
over peak and compulsory bytes over HBM bandwidth, counted from the
configuration's shapes at the executed bucket, ``chipbench/flops.py``),
over the device's busy time, per chip."""
from chipbench.flops import least_seconds


def read(ctx):
    t = ctx.trace
    if not t or ctx.peak is None or t["busy_s"] <= 0 or not ctx.steps:
        return None
    least = sum(least_seconds(ctx.cfg, rows // ctx.chips, ctx.peak)
                for _, _, _, rows in ctx.steps)
    return 100.0 * least / t["busy_s"]
