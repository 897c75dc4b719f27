"""Reduction of a profiler trace (``*.xplane.pb``) to device metrics.

* busy: the union of the intervals in which an operation ran on a device
  (the "XLA Ops" line of each ``/device:TPU:<i>`` plane), clipped to the
  measured window, averaged over the devices;
* per-kernel device time: the summed durations of the operations whose HLO
  instruction name is the kernel's name (``%proximity.1 = ...`` is the
  kernel ``proximity``);
* per-module device time: the summed durations of the "XLA Modules" events
  of each jitted program (``jit_local_sgd``), clipped to the window;
* the device operations that took most time, keyed ``<module>/<op>``;
* idle time by what the host was doing: each gap between busy intervals is
  split among the host spans (``span.<name>`` annotations) by how much of it
  each one covers as the innermost open span, and summed by span name; what
  no span covers is "no span".

The window is the host annotation ``span.window`` that the harness opens
around the measured loop.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

WINDOW_SPAN = "span.window"
_SUFFIX = re.compile(r"\.\d+$")
_HASH = re.compile(r"\(\d+\)$")


def op_name(event_name: str) -> str:
    """HLO instruction name without its numeric suffix: ``proximity``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def module_name(event_name: str) -> str:
    return _HASH.sub("", event_name.strip())


def union_length(intervals: list[tuple[int, int]]) -> int:
    """Total length covered by possibly overlapping [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _merged(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over devices
    n_devices: int
    kernel_s: dict[str, float]          # kernel -> summed device seconds
    kernel_calls: dict[str, int]
    module_s: dict[str, float]          # jitted program -> summed device seconds
    module_calls: dict[str, int]
    top_ops: list[tuple[str, float]]    # (module/op, seconds), longest first
    idle_by_host: list[tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def _innermost(host_spans, w0: int, w1: int) -> list[tuple[int, int, str]]:
    """Pieces ``(start, end, owner)`` that tile [w0, w1), each owned by the
    innermost host span open over it, or "no span".  Spans come from
    ``with`` blocks on one thread, so they nest, and a stack swept in start
    order holds exactly the open ones."""
    spans = sorted(
        (s, -e, n[len("span."):]) for n, s, e in host_spans if n != WINDOW_SPAN
    )
    pieces: list[tuple[int, int, str]] = []
    stack: list[tuple[int, str]] = []
    cursor = w0

    def emit(upto: int) -> None:
        nonlocal cursor
        upto = min(upto, w1)
        if upto > cursor:
            pieces.append((cursor, upto, stack[-1][1] if stack else "no span"))
            cursor = upto

    for s, neg_e, name in spans:
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((-neg_e, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(w1)
    return pieces


def _attributed_gaps(busy, host_spans, w0: int, w1: int):
    """Yield ``(span name, ns)`` for each part of each idle gap inside
    [w0, w1): a gap is split among the innermost spans open over it."""
    gaps, cursor = [], w0
    for s, e in list(busy) + [(w1, w1)]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    pieces = _innermost(host_spans, w0, w1)
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            ps, pe, owner = pieces[k]
            yield owner, min(ge, pe) - max(gs, ps)
            k += 1


def reduce_profile(pd, *, top: int = 10) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`."""
    host_spans: list[tuple[str, int, int]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(e for e in _events(line) if e[0].startswith("span."))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} '{WINDOW_SPAN}' annotations, want 1")
    if not devices:
        raise ValueError("trace holds no /device:TPU: plane")
    w0, w1 = windows[0]
    busy_total = 0
    kernel_ns: dict[str, int] = collections.Counter()
    kernel_calls: dict[str, int] = collections.Counter()
    op_ns: dict[str, int] = collections.Counter()
    module_ns: dict[str, int] = collections.Counter()
    module_calls: dict[str, int] = collections.Counter()
    first_busy: list[tuple[int, int]] = []
    for k, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        lines = {line.name: line for line in plane.lines}
        modules = sorted(
            (s, e, module_name(n)) for n, s, e in _events(lines["XLA Modules"])
        ) if "XLA Modules" in lines else []
        starts = [m[0] for m in modules]
        for s, e, mod in modules:
            if min(e, w1) > max(s, w0):
                module_ns[mod] += min(e, w1) - max(s, w0)
                module_calls[mod] += 1
        busy = []
        for name, s, e in _events(lines["XLA Ops"]) if "XLA Ops" in lines else ():
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            busy.append((s, e))
            op = op_name(name)
            kernel_ns[op] += e - s
            kernel_calls[op] += 1
            i = bisect.bisect_right(starts, s) - 1
            mod = modules[i][2] if i >= 0 and modules[i][1] >= s else "?"
            op_ns[f"{mod}/{op}"] += e - s
        busy_total += union_length(busy)
        if k == 0:
            first_busy = _merged(busy)
    n_dev = len(devices)
    idle: dict[str, int] = collections.Counter()
    for owner, length in _attributed_gaps(first_busy, host_spans, w0, w1):
        idle[owner] += length
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / n_dev * 1e-9,
        n_devices=n_dev,
        kernel_s={k: v * 1e-9 for k, v in kernel_ns.items()},
        kernel_calls=dict(kernel_calls),
        module_s={k: v * 1e-9 for k, v in module_ns.items()},
        module_calls=dict(module_calls),
        top_ops=[(k, v * 1e-9) for k, v in sorted(op_ns.items(), key=lambda t: -t[1])[:top]],
        idle_by_host=[(k, v * 1e-9) for k, v in sorted(idle.items(), key=lambda t: -t[1])[:top]],
    )


def reduce_file(path: str, **kw) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), **kw)
