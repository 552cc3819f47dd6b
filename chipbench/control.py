"""Readings of the comparison on many seeds in one process: the program's
(through the timed path, a short window at the cell's own load) and the
control's (the reference at the precision below the configuration's, in the
program's place), each against the reference at the configuration's
precision.  The limits in the configuration files are set from these.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 3 [--out readings.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from chipbench import reference as R  # noqa: E402
from chipbench.run import Cell, log  # noqa: E402

CONTROL = {"highest": "high", "high": "bf16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import numpy as np
    cell = Cell(args.workload)
    prec = cell.cfg["precision"]
    rows = []
    try:
        for seed in [int(s) for s in args.seeds.split(",")]:
            cell.prepare(seed)
            loop = cell.window(seed, args.seconds)
            served = [r for r in loop.requests if r[4] is not None]
            idx = [r[3] for r in served]
            got = np.stack([r[4] for r in served]).astype(np.float32)
            row = {"seed": seed, "served": len(served),
                   "requests": len(loop.requests)}
            refs, secs = {}, {}
            for p in (prec, CONTROL[prec], "bf16"):
                t = time.perf_counter()
                refs[p] = R.reference_probs(cell.params, cell.pool, cell.cfg,
                                            p, device=cell.devices[0])
                secs[p] = time.perf_counter() - t
            row["reference_s"] = secs
            row["program"] = R.check_numbers(got, refs[prec][idx])
            row["control"] = R.check_numbers(refs[CONTROL[prec]][idx],
                                             refs[prec][idx])
            row["bf16"] = R.check_numbers(refs["bf16"][idx],
                                          refs[prec][idx])
            log(json.dumps(row))
            rows.append(row)
    finally:
        cell.close()
    summary = {}
    for who in ("program", "control", "bf16"):
        for k in rows[0][who]:
            v = [r[who][k] for r in rows]
            summary[f"{who}.{k}"] = {"min": min(v), "max": max(v)}
    out = {"workload": args.workload, "rows": rows, "summary": summary}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
