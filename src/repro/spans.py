"""Program spans and compile counters, on the profiler's clock.

``span(name)`` marks one layer boundary of the FL round (``server.run``,
``engine.dispatch``, ...).  It always opens a
``jax.profiler.TraceAnnotation("repro.<name>")``, so any profiler trace
of the program (the benchmark's or an operator's own
``jax.profiler.trace``) shows the span beside the device's operations.
Nothing else happens unless a ``Recorder`` is attached with
``recording(recorder)``: then each span also appends a ``Record`` (name,
``time.perf_counter`` start and end, the enclosing span's name and the
round), the compile events jax reports add their seconds to the
recorder's counters, and ``count(name, key)`` bumps the counter
``(name, key)``.  With no recorder attached a span reads no clock, a
count keeps nothing, and no listener is registered with jax.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax

__all__ = ["COMPILE_EVENTS", "PREFIX", "Record", "Recorder", "count",
           "recording", "span"]

PREFIX = "repro."

# jax.monitoring's duration events of a compile: tracing to a jaxpr,
# lowering to MLIR, and the backend compile, which holds the lookup in
# the persistent compilation cache (timed on its own as the fourth)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


@dataclasses.dataclass(frozen=True)
class Record:
    name: str
    t0: float                  # time.perf_counter()
    t1: float
    parent: Optional[str]      # the enclosing span's name on this thread
    round: Optional[int]


@dataclasses.dataclass
class Recorder:
    """Spans and counters kept in memory while attached;
    ``counters[(event, fun_name)]`` is ``[seconds, count]``, and a
    program's own ``counters[(name, key)]`` (``count``) is ``[0.0,
    count]``."""
    spans: List[Record] = dataclasses.field(default_factory=list)
    counters: Dict[Tuple[str, str], List[float]] = dataclasses.field(
        default_factory=dict)

    def _count(self, event: str, seconds: float, **kwargs) -> None:
        if event in COMPILE_EVENTS:
            c = self.counters.setdefault(
                (event, str(kwargs.get("fun_name", ""))), [0.0, 0])
            c[0] += seconds
            c[1] += 1


class _Open(threading.local):
    """The names of this thread's open recorded spans."""

    def __init__(self):
        self.names: List[str] = []


_recorder: Optional[Recorder] = None
_open = _Open()


@contextlib.contextmanager
def span(name: str, round: Optional[int] = None) -> Iterator[None]:
    """The span ``name``, of FL round ``round`` where it has one; also a
    decorator (``@span("data.batch")``)."""
    with jax.profiler.TraceAnnotation(PREFIX + name):
        rec = _recorder
        if rec is None:
            yield
            return
        names = _open.names
        parent = names[-1] if names else None
        names.append(name)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            names.pop()
            rec.spans.append(Record(name, t0, t1, parent, round))


def count(name: str, key: str) -> None:
    """Count one ``key`` outcome of ``name`` (``engine.program_cache``
    ``hit``) in the attached recorder; nothing without one."""
    rec = _recorder
    if rec is not None:
        rec.counters.setdefault((name, key), [0.0, 0])[1] += 1


@contextlib.contextmanager
def recording(recorder: Recorder) -> Iterator[Recorder]:
    """Attach ``recorder`` for the block: spans append to it and the
    compile events count into it."""
    global _recorder
    prev = _recorder
    listener = recorder._count
    _recorder = recorder
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield recorder
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
        _recorder = prev
