"""Record the small chip trace that ``test_trace_reduce.py`` reads.

    python3 benchmarks/chip/tests/record_trace.py

Runs ``boot.femnist-eq3`` traced for one second at K=512 on the TPU and
keeps its trace, xz-compressed, as ``tests/data/boot_k512.xplane.pb.xz``,
with the run's own reduction beside it (``boot_k512.json``) for the test to
compare against.
"""
import dataclasses
import json
import lzma
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

if __name__ == "__main__":
    out = HERE / "data" / "boot_k512.xplane.pb"
    out.parent.mkdir(exist_ok=True)
    line = harness.run_cell(
        "boot.femnist-eq3", 11, 1.0, True, t_start=time.perf_counter(),
        config_overrides={"n_clients": 512}, keep_trace=out,
    )
    summary = trace_reduce.reduce_file(out)
    out.with_name(out.name + ".xz").write_bytes(lzma.compress(out.read_bytes(), preset=9))
    out.unlink()
    (HERE / "data" / "boot_k512.json").write_text(
        json.dumps({"line": line, "summary": dataclasses.asdict(summary)}, indent=1) + "\n"
    )
    print(json.dumps(line))
