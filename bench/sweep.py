"""Find an open-loop cell's knee once: the highest arrival rate the system
sustains, by a sweep on the chip, in one process.

    python3 bench/sweep.py --workload <name> --rates 4,8,12 --seconds 30

For each rate, one run of the cell at that rate (the warm-up only before
the first) and the window's queue: requests due, requests with no prefill
started at the close, and the median TTFT of requests due in the first and
the second half of the window. A rate is sustained when the queue at the
close is shorter than the slots and the second half's median TTFT is not
more than twice the first half's. The cell's rate is then set to about
0.8 of the highest sustained rate. One JSON line per rate.
"""
from __future__ import annotations

import time

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    from benchkit import harness

    for i, r in enumerate(float(x) for x in args.rates.split(",")):
        t = time.perf_counter()
        try:
            out = harness.run_cell(args.workload, args.seed + i, args.seconds,
                                   False, t_start=t, warm=i == 0, rate=r)
        except harness.NoChip as e:
            harness.log(f"no run: {e}")
            return 2
        print(json.dumps({"rate": r, "queue": out["queue"],
                          "metrics": out["metrics"],
                          "correct": out["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
