"""Per-step readings of the program's own spans (``repro.tracing``).

The program records a span at each of its layer boundaries whenever a
profiler session is active, so in a traced run ``repro.tracing.records()``
holds the spans of the measured window.  A reader takes a span's self time
(its duration less the part its child spans cover) or a count attached to
it, summed over the window and divided by the window's steps.  Where the
program has no such span (one without ``repro.tracing``, or a span off the
path) the reading is ``None``, so the metric is left out, never 0.
"""
from __future__ import annotations

import collections
from typing import Iterable, Optional


def program_records() -> Optional[list]:
    """The program's span records, or ``None`` if it keeps none."""
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing.records()


def self_ns(recs: Iterable, name: str) -> Optional[int]:
    """Summed self time (ns) of the spans called ``name``; ``None`` if none."""
    recs = list(recs)
    covered: dict[int, int] = collections.Counter()
    for r in recs:
        if r.parent is not None:
            covered[r.parent] += r.end_ns - r.start_ns
    own = [r.end_ns - r.start_ns - covered[r.id] for r in recs if r.name == name]
    return sum(own) if own else None


def counted(recs: Iterable, name: str, key: str) -> Optional[int]:
    """Summed count ``key`` of the spans called ``name``; ``None`` if no such
    span carries it."""
    vals = [r.counts[key] for r in recs if r.name == name and key in r.counts]
    return sum(vals) if vals else None


def self_ms_per_step(run, name: str) -> Optional[float]:
    recs = program_records()
    ns = self_ns(recs, name) if recs else None
    return None if ns is None else 1e-6 * ns / run.steps


def count_per_step(run, name: str, key: str) -> Optional[float]:
    recs = program_records()
    n = counted(recs, name, key) if recs else None
    return None if n is None else n / run.steps
