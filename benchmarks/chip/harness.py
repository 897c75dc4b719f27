"""The general harness: one cell of ``BENCHMARK.json``, one run.

A cell names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the mix names its driver
(``drivers/<driver>.py``), and each per-layer metric has a reader of its own
(``metrics/<metric>.py``).  The harness finds all of them by name, so a new
cell, mix, configuration or metric is new files plus new entries.

A driver module exposes ``make(run) -> cell``; the cell has

* ``setup()``: make the data from the seed, build the program's state and
  warm up every shape the window will use (counted in ``setup_s``);
* ``window(seconds)``: the measured loop; it sets ``run.e2e[<metric>]``,
  ``run.attempted``, ``run.failed``, ``run.steps`` and ``run.step_s``;
* ``check()``: after the window, compares what the timed path produced with
  the plain reference; returns ``[(name, value, limit), ...]``, each passing
  where ``value <= limit``.

A metric reader module exposes ``read(run) -> float | None`` (``None``:
nothing to read in this run, and the metric is left out of the line) and may
declare ``SPANS = {name: "package.module:attr"}``, the program calls it
needs wrapped in host spans in a traced run.  Readers that need the same
span declare the same name and target; it is wrapped once.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

from spans import Spans  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Run:
    """What one run knows and what its cell reports; metric readers read it."""

    seed: int
    config: dict
    traffic: dict
    peak: Optional[dict]
    spans: Spans
    control: bool = False
    e2e: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    steps: int = 0
    step_s: list = dataclasses.field(default_factory=list)
    trace_summary: Any = None


def cell_spec(spec: dict, name: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    wl = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return wl, configs[wl["config"]]


def _listed(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports the end-to-end ``metric``: the cells its
    ``workloads`` key lists; without the key, every cell."""
    return cell in metric.get("workloads", [cell])


def device_info(n_chips: int, require_tpu: bool) -> tuple[dict, list]:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < n_chips):
        raise NoChip(
            f"cell needs {n_chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)"
        )
    used = devs[:n_chips]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }, used


def _compile_counter():
    """Count compile requests from now on (JAX's monitoring events)."""
    import jax

    seen = {"n": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            seen["n"] += 1

    jax.monitoring.register_event_listener(on_event)
    return seen


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set (JAX
    reads it itself), else ``<checkout>/.jax_cache``, a fixed path."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    require_tpu: bool = True,
    config_overrides: Optional[dict] = None,
    control: bool = False,
    keep_trace: Optional[Path] = None,
    log=sys.stderr,
) -> dict:
    """Run one cell and return the result line as a dict."""
    spec = load_json(ROOT / "BENCHMARK.json")
    wl, cfg_entry = cell_spec(spec, workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    device, used = device_info(int(wl["chips"]), require_tpu)
    enable_compile_cache()
    peaks = load_json(HERE / "peaks.json")["kinds"]
    if require_tpu and device["kind"] not in peaks:
        raise KeyError(f"device kind {device['kind']!r} is not in peaks.json")
    config = load_json(ROOT / cfg_entry["file"])
    config.update(config_overrides or {})
    traffic = load_json(HERE / "traffic" / f"{wl['traffic']}.json")
    run = Run(
        seed=seed, config=config, traffic=traffic, peak=peaks.get(device["kind"]),
        spans=Spans(), control=control,
    )
    e2e = [m for m in spec["end_to_end"] if _listed(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if workload in m["workloads"]]
    readers = {m["name"]: load_module("metrics", m["name"]) for m in layer} if trace else {}
    wrapped: dict[str, str] = {}
    for reader in readers.values():
        for span_name, target in getattr(reader, "SPANS", {}).items():
            if wrapped.setdefault(span_name, target) != target:
                raise ValueError(f"span {span_name!r} names both {wrapped[span_name]} and {target}")
    for span_name, target in wrapped.items():
        run.spans.install(span_name, target)

    cell = load_module("drivers", traffic["driver"]).make(run)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    compiles = _compile_counter()
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        run.spans.on = True
    try:
        with run.spans.span("window"):
            cell.window(seconds)
    finally:
        if trace:
            run.spans.on = False
            jax.profiler.stop_trace()
    window_compiles = compiles["n"]
    peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used
    )
    device["memory_peak_bytes"] = int(peak_bytes)
    run.spans.restore()

    result: dict[str, Any] = {}
    if trace:
        import trace_reduce

        files = list(Path(trace_dir).rglob("*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"profiler wrote {len(files)} trace files")
        run.trace_summary = trace_reduce.reduce_file(files[0])
        if keep_trace is not None:
            shutil.copy(files[0], keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ts = run.trace_summary
        device["busy_s"] = ts.busy_s
        device["window_s"] = ts.window_s
        metrics = {}
        for m in layer:
            value = readers[m["name"]].read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in ts.top_ops],
            "idle_gaps": [[k, v] for k, v in ts.idle_by_host],
        }
    else:
        run.e2e["setup_s"] = setup_s
        missing = e2e_names - set(run.e2e)
        if missing:
            raise RuntimeError(f"driver reported no value for {sorted(missing)}")
        metrics = {
            m["name"]: {"value": float(run.e2e[m["name"]]), "unit": m["unit"]}
            for m in e2e
        }

    checks = [(name, float(value), limit) for name, value, limit in cell.check()]
    correct = run.failed == 0 and all(v <= lim for _, v, lim in checks)
    print(
        f"[{workload}] seed={seed} steps={run.steps} attempted={run.attempted} "
        f"failed={run.failed} setup_s={setup_s:.3f} compiles_in_window={window_compiles}",
        file=log,
    )
    for name, value, limit in checks:
        print(f"check {name}: {value!r} (limit {limit!r})", file=log)
    log.flush()
    line = {
        "correct": bool(correct),
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": device,
    }
    line.update(result)
    line["compiles_in_window"] = window_compiles
    line["checks"] = {name: {"value": value, "limit": limit} for name, value, limit in checks}
    return line
