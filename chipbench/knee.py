"""Sweep the offered rate of an open-loop cell in one process, to find the
highest rate the server sustains without a growing backlog.

    python3 chipbench/knee.py --workload alexnet.online --seed 7 \
        --seconds 6 --rates 400,600,800

Per rate it prints the requests offered and served, the served rate, the
p50 and p95 latency, and the drain: how long the last requests due inside
the window took to finish after it closed.  A drain of more than a few
steps means the queue grew through the window.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from chipbench.run import Cell, log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import numpy as np
    cell = Cell(args.workload)
    rows = []
    try:
        cell.prepare(args.seed)
        for rate in [float(r) for r in args.rates.split(",")]:
            cell.mix = dict(cell.mix, rate=rate)
            loop = cell.window(args.seed, args.seconds)
            lat = np.array([r[2] - r[0] for r in loop.requests])
            sizes = [n for _, _, n, _ in loop.steps]
            row = {"rate": rate, "offered": len(loop.requests),
                   "served_per_s": len(loop.requests) / loop.window_s,
                   "p50_ms": 1e3 * float(np.percentile(lat, 50)),
                   "p95_ms": 1e3 * float(np.percentile(lat, 95)),
                   "p95_first_half_ms": 1e3 * float(np.percentile(
                       lat[:len(lat) // 2], 95)),
                   "p95_second_half_ms": 1e3 * float(np.percentile(
                       lat[len(lat) // 2:], 95)),
                   "drain_s": loop.window_s - args.seconds,
                   "steps": len(loop.steps),
                   "mean_batch": float(np.mean(sizes)),
                   "step_ms_median": 1e3 * float(np.median(
                       [e - s for s, e, _, _ in loop.steps]))}
            log(json.dumps(row))
            rows.append(row)
    finally:
        cell.close()
    print(json.dumps(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
