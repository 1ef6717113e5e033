"""Host spans, on the profiler's clock.

Each span is written into the profiler trace as a
``jax.profiler.TraceAnnotation`` (so a traced run puts it on the device
trace's clock) and kept in memory with its host times, so the per-layer
readers can use it without a trace too.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

import jax

PREFIX = "bench."


class Spans:
    def __init__(self):
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def wrap(self, obj, method: str, name: str) -> None:
        """Put a span around a bound method of one instance."""
        inner = getattr(obj, method)

        def wrapped(*a, **k):
            with self.span(name):
                return inner(*a, **k)
        setattr(obj, method, wrapped)

    def total(self, name: str, t0: float = None, t1: float = None) -> float:
        """Seconds inside spans called ``name`` that start in [t0, t1]."""
        return sum(b - a for n, a, b in self.records
                   if n == name and (t0 is None or a >= t0)
                   and (t1 is None or a <= t1))
