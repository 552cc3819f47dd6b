"""Open loop: independent users, requests due on a schedule whatever the
server does.  Parameters (the mix's file):

    {"kind": "open", "rate": 1000.0, "max_bucket": 32}

``rate`` is the mean number of requests per second over all chips.
Arrivals are Poisson: the gaps between them are the quantiles of the
exponential distribution at that rate, shuffled by the seed, so every seed
offers the same set of gaps in another order.  Every request due inside
the window is served; those still queued at the close are drained and
counted.
"""
from __future__ import annotations

import numpy as np


def warm_sizes(mix, chips):
    """Every batch size up to the largest: arrivals make any of them, and
    the server pads each admitted size to its bucket with an operation of
    that size's own shape."""
    return list(range(1, mix["max_bucket"] * chips + 1))


def arrivals(mix, seconds, seed):
    """Due times (seconds from the window's start) of the requests."""
    rate = mix["rate"]
    n = int(round(rate * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)                       # unit-rate exponential
    np.random.default_rng(seed).shuffle(gaps)
    due = np.cumsum(gaps / rate)
    return due[due < seconds].tolist()


def drive(loop, mix, seconds):
    due = arrivals(mix, seconds, loop.seed)
    i, n = 0, len(due)
    while i < n or loop.queued():
        now = loop.now()
        if i < n and due[i] <= now:
            with loop.span("bench.admit"):
                while i < n and due[i] <= now:
                    loop.submit(due=due[i])
                    i += 1
        if loop.queued():
            loop.step()
        elif i < n:
            with loop.span("bench.wait"):
                loop.sleep_until(due[i])
