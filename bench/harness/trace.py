"""Reduce a profiler trace (``.xplane.pb``) to what the readers need.

The profiler writes one plane per device (``/device:TPU:<i>``) whose
``XLA Ops`` line holds every operation the chip ran, with its start and
duration in nanoseconds, and host planes whose lines hold the
benchmark's ``bench.<name>`` annotations (``spans.py``) on the same
clock.  From those:

- the window: the ``bench.window`` annotation;
- busy time per device: the union of its operations' intervals inside
  the window (``busy_s`` averages it over the devices);
- per-operation totals on the first device, by HLO instruction name,
  and kernel time by a name in the operation's text or stats;
- the idle gaps of the first device, each put to the innermost
  ``bench.*`` span that covers the gap's middle (``host`` when none
  does), summed by span.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

from .spans import PREFIX

WINDOW = PREFIX + "window"
_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MOSAIC = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    dur_ns: float
    text: str          # name and every string stat, for matching kernels


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over devices
    n_devices: int
    op_s: Dict[str, float]              # first device, inside the window
    idle_by_span: Dict[str, float]      # first device's idle, by host span
    ops: List[Op]                       # first device, inside the window
    spans: List[Tuple[str, float, float]]   # host spans, ns

    def kernel_s(self, kernel: str) -> Tuple[float, int]:
        """Seconds and count of first-device operations that run the
        Pallas kernel ``kernel``: Mosaic custom calls whose HLO instruction
        is ``<kernel>.<n>``, after the function that calls the kernel."""
        hits = [o for o in self.ops if MOSAIC in o.text
                and o.name.rsplit(".", 1)[0] == kernel]
        return sum(o.dur_ns for o in hits) * 1e-9, len(hits)

    def breakdown(self, k: int = 10) -> Dict[str, list]:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:k]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _stat_text(event) -> str:
    parts = [event.name]
    for key, value in event.stats:
        if isinstance(value, str):
            parts.append(f"{key}={value}")
    return " ".join(parts)


def union_ns(intervals: Iterable[Tuple[float, float]], lo: float,
             hi: float) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of ``(start, end)`` intervals clipped to
    [lo, hi], and the merged intervals."""
    clipped = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                     if b > lo and a < hi)
    merged: List[Tuple[float, float]] = []
    for a, b in clipped:
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return sum(b - a for a, b in merged), merged


def _device_planes(pd) -> list:
    planes = [(int(m.group(2)), p) for p in pd.planes
              if (m := _DEVICE.match(p.name))]
    return [p for _, p in sorted(planes, key=lambda ip: ip[0])]


def _short(name: str) -> str:
    """An operation's HLO instruction name (``fusion.3``) from the long
    form a TPU trace gives it (``%fusion.3 = f32[8]{0} fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _ops(plane) -> List[Op]:
    for line in plane.lines:
        if line.name == OPS_LINE:
            return [Op(_short(e.name), e.start_ns, e.duration_ns,
                       _stat_text(e))
                    for e in line.events]
    return []


def _host_spans(pd) -> List[Tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name[len(PREFIX):], e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def _innermost(spans, t: float) -> str:
    best: Optional[Tuple[str, float, float]] = None
    for name, a, b in spans:
        if name != "window" and a <= t <= b and (
                best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "host"


def reduce_profile(pd) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` (see module docstring)."""
    spans = _host_spans(pd)
    windows = [(a, b) for n, a, b in spans if n == "window"]
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    lo, hi = windows[-1]
    planes = _device_planes(pd)
    if not planes:
        raise ValueError("trace has no device plane: no operation ran on "
                         "an accelerator")
    busy = []
    first_ops: List[Op] = []
    merged0: List[Tuple[float, float]] = []
    for i, plane in enumerate(planes):
        ops = _ops(plane)
        total, merged = union_ns(((o.start_ns, o.start_ns + o.dur_ns)
                                  for o in ops), lo, hi)
        busy.append(total)
        if i == 0:
            first_ops = [o for o in ops
                         if o.start_ns < hi and o.start_ns + o.dur_ns > lo]
            merged0 = merged
    op_s: Dict[str, float] = defaultdict(float)
    for o in first_ops:
        op_s[o.name] += o.dur_ns * 1e-9
    idle: Dict[str, float] = defaultdict(float)
    inner = [s for s in spans if s[0] != "window" and s[2] > lo and s[1] < hi]
    edges = [lo] + [x for ab in merged0 for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            idle[_innermost(inner, 0.5 * (a + b))] += (b - a) * 1e-9
    return TraceSummary(window_s=(hi - lo) * 1e-9,
                        busy_s=sum(busy) / len(busy) * 1e-9,
                        n_devices=len(planes), op_s=dict(op_s),
                        idle_by_span=dict(idle), ops=first_ops, spans=inner)


def load(path: str):
    """``ProfileData`` from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)
