"""Share of the device's busy time spent in operations that are not Pallas
kernels (the XLA copies around each kernel, the fc dots, the softmax's
neighbours), as the trace classifies them (``chipbench/trace.py``)."""


def read(ctx):
    t = ctx.trace
    if not t or t["busy_s"] <= 0:
        return None
    return 100.0 * t["other_s"] / (t["pallas_s"] + t["other_s"])
