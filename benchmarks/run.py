"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Accuracy tables read the recorded
experiment-suite JSONs (experiments/run_fl_suite.py); everything else runs
live at quick scale.

Usage: PYTHONPATH=src python -m benchmarks.run [--full]
"""
import argparse
import sys
import time
import traceback

from benchmarks.common import emit


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    quick = not args.full

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        fig2_beta_sweep,
        kernels_bench,
        proximity_scale,
        roofline_table,
        table1_proximity,
        table4_newcomers,
        table5_comm_cost,
        table6_gaussian,
        table_accuracy,
    )

    suites = {
        "table1": table1_proximity.run,
        "accuracy": table_accuracy.run,       # tables 2/3/7/8
        "table4": table4_newcomers.run,
        "table5": table5_comm_cost.run,       # tables 5/9/10
        "fig2": fig2_beta_sweep.run,
        "table6": table6_gaussian.run,
        "kernels": kernels_bench.run,
        "proximity_scale": proximity_scale.run,
        "roofline": roofline_table.run,
    }
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            rows = fn(quick=quick)
            emit(rows)
            emit([(f"{name}/__suite_seconds", None, f"{time.time()-t0:.1f}")])
        except Exception:
            traceback.print_exc()
            emit([(f"{name}/__suite_error", None, "see stderr")])
            failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
