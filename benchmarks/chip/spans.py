"""Host spans recorded from the benchmark's own files.

In a traced run the harness wraps named program calls (``module:attr``
targets that the per-layer metric readers declare in ``SPANS``) so that each
call is timed on the host clock and, as a ``jax.profiler.TraceAnnotation``
named ``span.<name>``, lands in the profiler's trace on the same clock as the
device's operations.  Nothing is wrapped in an untraced run.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import importlib
import inspect
import time


class Spans:
    """Span durations (seconds) by name, recorded while ``on``."""

    def __init__(self):
        self.durations: dict[str, list[float]] = collections.defaultdict(list)
        self.on = False
        self._patches: list[tuple[object, str, object]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.on:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(f"span.{name}"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.durations[name].append(time.perf_counter() - t0)

    def install(self, name: str, target: str) -> None:
        """Wrap ``target`` ("package.module:Class.method" or
        "package.module:function") in a span called ``name``."""
        mod_name, _, path = target.partition(":")
        owner = importlib.import_module(mod_name)
        *parents, attr = path.split(".")
        for part in parents:
            owner = getattr(owner, part)
        original = inspect.getattr_static(owner, attr)
        if isinstance(original, (staticmethod, classmethod)):
            raise TypeError(f"span target {target} must be a plain function or method")

        @functools.wraps(original)
        def wrapped(*args, **kwargs):
            with self.span(name):
                return original(*args, **kwargs)

        setattr(owner, attr, wrapped)
        self._patches.append((owner, attr, original))

    def restore(self) -> None:
        for owner, attr, original in reversed(self._patches):
            setattr(owner, attr, original)
        self._patches.clear()
