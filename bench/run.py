"""Benchmark entry point: one run of one cell, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's model from ``--seed`` (random weights, made on the
device), serves its traffic through the repository's continuous-batching
engine, warms every program the traffic can reach, measures for
``--seconds``, checks the served tokens against the plain float32
reference and prints one JSON object as the last line of standard output.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (profiler trace of the window's end). Exits non-zero,
printing no result, when JAX finds no TPU or fewer chips than the cell
asks for, or when the system under test is not beside the benchmark.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchkit import harness

    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except (harness.NoChip, FileNotFoundError) as e:
        harness.log(f"no run: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
