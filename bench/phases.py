"""Device idle time of one traced run of a cell, by program phase.

    python3 bench/phases.py --workload <name> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and prints that run's
result as the last line of standard output, with the breakdown's
``idle_spans`` added: the idle seconds of the traced window under each
program phase (``benchkit/phases.py``), largest first. Exits non-zero,
printing no result, where ``bench/run.py`` would.
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
    args = ap.parse_args(argv)
    from benchkit import harness, phases, trace

    # the harness deletes the profile once reduced: read the phases from
    # the same loaded trace on its way to the reducer
    reduce, found = trace.reduce, []

    def reduce_and_keep(t, **kw):
        found.append(phases.idle_spans(t, **kw))
        return reduce(t, **kw)

    trace.reduce = reduce_and_keep
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds, True,
                               t_start=T_START)
    except (harness.NoChip, FileNotFoundError) as e:
        harness.log(f"no run: {e}")
        return 2
    finally:
        trace.reduce = reduce
    if found and "breakdown" in out:
        out["breakdown"]["idle_spans"] = found[-1]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
