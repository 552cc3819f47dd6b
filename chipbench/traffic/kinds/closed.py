"""Closed loop: offline batch classification.

Before every step the queue is topped up to ``depth`` requests, so the
server always finds a full batch.  Parameters (the mix's file):

    {"kind": "closed", "depth": 128, "max_bucket": 128}

``max_bucket`` is the largest batch one chip runs; ``depth`` is the global
batch, ``max_bucket`` times the cell's chips.
"""
from __future__ import annotations


def warm_sizes(mix, chips):
    """Global batch sizes whose buckets the window uses."""
    return [mix["depth"]]


def drive(loop, mix, seconds):
    """Keep the queue at ``depth`` and step until ``seconds`` have passed;
    the step that is running at the close finishes inside the window."""
    depth = mix["depth"]
    while loop.now() < seconds:
        with loop.span("bench.admit"):
            while loop.queued() < depth:
                loop.submit(due=loop.now())
        loop.step()
