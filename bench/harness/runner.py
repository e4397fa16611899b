"""One run of one cell: set-up, the measured window, the readers, the
comparison with the reference, and the result line.

``run_cell`` takes the devices it may use and never looks for a chip
itself (``bench/run.py`` does), so the tests drive it on the CPU.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import jax

from .cells import Cell
from .compare import Check, load_limits
from .peaks import Peaks
from .spans import Spans
from . import trace as trace_lib

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


@dataclasses.dataclass
class Context:
    """What a metric reader may read (``bench/metrics/<name>.py``)."""
    cell: Cell
    system: Any
    window: Any                  # the system's Window
    setup_s: float
    spans: Spans
    peaks: Peaks
    chips: int
    trace: Optional[trace_lib.TraceSummary] = None


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def _traced_window(system, seconds: float):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with tempfile.TemporaryDirectory() as out:
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            window = system.window(seconds)
        finally:
            jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        summary = trace_lib.reduce_profile(trace_lib.load(paths[-1]))
    return window, summary


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices: List, peaks: Peaks, t_start: float) -> Dict[str, Any]:
    """Run ``cell`` and return its result line as a dict (``checks``
    last).  ``t_start`` is when the process started its set-up."""
    devices = list(devices)[:cell.chips]
    spans = Spans()
    system = cell.system().System(cell, seed, spans, devices)
    system.setup()
    setup_s = time.perf_counter() - t_start
    summary = None
    if trace:
        window, summary = _traced_window(system, seconds)
    else:
        window = system.window(seconds)
    memory_peak = _memory_peak(devices)
    system.release()

    ctx = Context(cell=cell, system=system, window=window, setup_s=setup_s,
                  spans=spans, peaks=peaks, chips=cell.chips, trace=summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.reader().read(ctx)
        if value is not None:
            metrics[m.name] = {"value": float(value), "unit": m.unit}

    checks: List[Check] = system.checks(cell.reference(),
                                        load_limits(cell.name))
    correct = all(c.ok for c in checks)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {
        "correct": correct, "attempted": window.rounds,
        "failed": 0 if correct else window.rounds,
        "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def print_result(result: Dict[str, Any]) -> None:
    """Each compared number beside its limit as the last lines on
    standard error, then the result as the last line of standard
    output."""
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
