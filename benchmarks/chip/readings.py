"""Readings that a cell's limits are set from: the numbers that decide
``correct``, for the program on many seeds and for the control on a few.

    python3 benchmarks/chip/readings.py --workload boot.femnist-eq3 \
        --seeds 1 2 3 --control-seeds 4 5 6 --seconds 3

All runs share one process (set-up of each still runs in full).  The control
is the plain reference put in the program's place at one precision step
below the configuration's (``reference.py``); it has to read as not
correct.  One JSON line per run: ``{"mode", "seed", "correct", "checks"}``.
"""
from __future__ import annotations

import argparse
import io
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def readings(workload, seeds, control_seeds, seconds, **kw):
    for mode, seed_list in (("program", seeds), ("control", control_seeds)):
        for seed in seed_list:
            line = harness.run_cell(
                workload, seed, seconds, False, t_start=time.perf_counter(),
                control=mode == "control", log=io.StringIO(), **kw,
            )
            yield {"mode": mode, "seed": seed, "correct": line["correct"],
                   "checks": line["checks"], "metrics": line["metrics"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    for rec in readings(args.workload, args.seeds, args.control_seeds, args.seconds):
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
