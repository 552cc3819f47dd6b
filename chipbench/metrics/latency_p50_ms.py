"""Median request latency over every request due in the window: from the
time it was due to the moment its probabilities were on the host.  Requests
still queued at the close are drained and counted; a request never served
counts as infinitely late."""
import numpy as np


def read(ctx):
    if not ctx.requests:
        return None
    lat = [r[2] - r[0] if r[2] is not None else float("inf")
           for r in ctx.requests]
    return 1e3 * float(np.percentile(lat, 50))
