"""Chip benchmark entry point: one cell, one run, one JSON line.

    python3 benchmarks/chip/run.py --workload boot.femnist-eq3 --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout on a machine with the cell's TPU chips.  The
last stdout line is the result (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``; with ``--trace 1`` also ``breakdown``), and the
numbers compared for ``correct`` end both that line (``checks``) and the
standard error.  With no TPU, or fewer chips than the cell asks for, it
prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
# libtpu would otherwise keep its logs under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import harness

    try:
        line = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START
        )
    except harness.NoChip as e:
        print(f"run.py: {e}; no result", file=sys.stderr)
        return 2
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
