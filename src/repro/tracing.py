"""Spans at the program's layer boundaries, on the profiler's clock.

``span(name)`` is a context manager put around host call sites (never inside
a jitted body).  While no ``jax.profiler`` session is active it checks
:meth:`jax.profiler.TraceAnnotation.is_enabled`, returns the shared no-op
handle :data:`NO_SPAN` and records nothing.  While one is active (a
``jax.profiler.trace`` block, or ``start_trace`` .. ``stop_trace``) it

* opens ``jax.profiler.TraceAnnotation("span.<name>")``, so the span lands in
  the profiler's host plane on the same clock as the device's operations;
* keeps a :class:`SpanRecord` in a bounded in-memory ring: name, id, parent
  id (the enclosing span on this thread), root id (the outermost enclosing
  span), start and end in ns on the profiler's clock (``time.time_ns``),
  and the counts the caller set with ``handle.count(key, n)`` before close.

``records()`` returns the ring's records, oldest first; ``dropped()`` says
how many older ones the ring let go; ``reset()`` clears both.  The
profiler's trace is the export: there is nothing to configure.

Span names are dotted, ``<layer>.<part>`` (``engine.hc``, ``store.remove``),
so they never collide with spans a caller opens around the program.

    import jax
    from repro import tracing

    with jax.profiler.trace(trace_dir):
        ClusterEngine.from_signatures(U, cfg)
    for r in tracing.records():
        print(r.name, (r.end_ns - r.start_ns) / 1e6, r.counts)
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

from jax.profiler import TraceAnnotation

RING_SIZE = 65_536

_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_ring_lock = threading.Lock()
_dropped = 0
_ids = itertools.count(1)
_local = threading.local()


@dataclass(slots=True)
class SpanRecord:
    """One closed span (``end_ns`` is 0 while it is still open)."""

    name: str
    id: int
    parent: Optional[int]
    root: int
    start_ns: int = 0
    end_ns: int = 0
    counts: dict = field(default_factory=dict)

    def count(self, key: str, n: int) -> None:
        """Attach a count to the span (read at close)."""
        self.counts[key] = int(n)


class _NoSpan:
    """The handle of a span while tracing is off: does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def count(self, key: str, n: int) -> None:
        return None


NO_SPAN = _NoSpan()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("_name", "_record", "_annotation")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self) -> SpanRecord:
        stack = _stack()
        sid = next(_ids)
        parent = stack[-1] if stack else None
        rec = SpanRecord(
            self._name, sid,
            parent.id if parent is not None else None,
            parent.root if parent is not None else sid,
        )
        self._annotation = TraceAnnotation(f"span.{self._name}")
        self._annotation.__enter__()
        rec.start_ns = time.time_ns()
        stack.append(rec)
        self._record = rec
        return rec

    def __exit__(self, *exc) -> None:
        rec = self._record
        rec.end_ns = time.time_ns()
        self._annotation.__exit__(*exc)
        _stack().pop()
        _keep(rec)
        return None


def _keep(rec: SpanRecord) -> None:
    global _dropped
    with _ring_lock:
        if len(_ring) == _ring.maxlen:
            _dropped += 1
        _ring.append(rec)


def span(name: str):
    """Context manager timing one host call site as ``name``; its handle's
    ``count(key, n)`` attaches a count.  A no-op unless a profiler session is
    active."""
    if not TraceAnnotation.is_enabled():
        return NO_SPAN
    return _Span(name)


def records() -> list[SpanRecord]:
    """The closed spans held in the ring, oldest first."""
    with _ring_lock:
        return list(_ring)


def dropped() -> int:
    """Records the ring let go to make room since the last :func:`reset`."""
    return _dropped


def reset() -> None:
    """Forget every record and the dropped count."""
    global _dropped
    with _ring_lock:
        _ring.clear()
        _dropped = 0
