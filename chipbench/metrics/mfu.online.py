"""Model FLOP utilisation while serving: FLOPs of the real (not padded)
images served, over the summed wall time of the server's steps times the
chips' bf16 peak."""
from chipbench.flops import flops_per_image


def read(ctx):
    busy = sum(end - start for start, end, _, _ in ctx.steps)
    images = sum(n for _, _, n, _ in ctx.steps)
    if ctx.peak is None or busy <= 0 or not images:
        return None
    return 100.0 * images * flops_per_image(ctx.cfg) / (
        busy * ctx.chips * ctx.peak["flops_per_s"])
