"""The benchmark's host spans: one clock for the per-layer readers, and
the same spans in the profiler's trace.

Each span is timed on ``time.perf_counter`` and is also a
``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so a traced run
puts it on the clock of the device's events and the trace reduction can
say what the host was doing while the chip sat idle.  Spans are kept in
memory and read once the window has closed.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, List, Tuple

import jax

PREFIX = "bench."


class Spans:
    def __init__(self):
        self.records: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
        self.records.append((name, t0, time.perf_counter()))

    def wrap(self, name: str, fn: Callable) -> Callable:
        """``fn`` with every call inside a span called ``name``."""
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped

    def total_s(self, name: str, lo: float, hi: float) -> float:
        """Seconds spent in spans called ``name``, clipped to [lo, hi]."""
        return sum(max(0.0, min(t1, hi) - max(t0, lo))
                   for n, t0, t1 in self.records if n == name)

    def count(self, name: str, lo: float, hi: float) -> int:
        return sum(1 for n, t0, t1 in self.records
                   if n == name and t0 >= lo and t1 <= hi)
