"""Readings that set a cell's correctness limit, in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 51 \
        --control-seeds 3

For each seed: one run of the cell as ``bench/run.py`` makes it (warmed up
before the first seed only: the programs stay loaded), the served tokens'
gaps under the plain float32 reference and, for the first
``--control-seeds`` seeds, the control's: the reference put in the
program's place one precision lower (``check.CONTROL``), read at the same
positions and held to the cell's limits as the program is. One JSON line
per seed on standard output. The lower reading of a limit is the largest
program reading over a dozen seeds or more, the upper the smallest
control reading.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args(argv)
    from benchkit import harness

    for i, s in enumerate(args.seeds.split(",")):
        t = time.perf_counter()
        try:
            out = harness.run_cell(args.workload, int(s), args.seconds, False,
                                   t_start=t, warm=i == 0,
                                   control=i < args.control_seeds,
                                   readings=True,
                                   rate=args.rate)
        except harness.NoChip as e:
            harness.log(f"no run: {e}")
            return 2
        print(json.dumps({"seed": int(s), "correct": out["correct"],
                          "compared": out["compared"],
                          "program": out.get("gaps"),
                          "control_correct": out.get("control_correct"),
                          "control_compared": out.get("control_compared"),
                          "control": out.get("control"),
                          "metrics": out["metrics"],
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
