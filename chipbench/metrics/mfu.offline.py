"""Model FLOP utilisation of the whole step: images per second in the
traced window times the configuration's FLOPs per image (convolutions and
fully-connected layers, ``chipbench/flops.py``), over the chips' bf16
peak."""
from chipbench.flops import flops_per_image


def read(ctx):
    done = sum(1 for r in ctx.requests if r[2] is not None)
    if ctx.peak is None or not done:
        return None
    rate = done / ctx.window_s
    return 100.0 * rate * flops_per_image(ctx.cfg) / (
        ctx.chips * ctx.peak["flops_per_s"])
