"""From a profiler trace (``.xplane.pb``) to device busy time, idle gaps and
time per XLA program.

- Busy: the union of the intervals of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane, clipped to the traced window, averaged over
  the chips used. Idle share = 1 - busy / window.
- Window: the harness's own ``TraceAnnotation`` named ``window`` on the
  host plane; without it, first to last event of the trace.
- Time per program: the ``XLA Modules`` line, summed by module name with
  the trailing ``(<fingerprint>)`` taken off.
- Idle gaps: the complement of busy within the window, each named by the
  harness span (``feed``, ``settle``, ``wait``, ...) that covers its
  midpoint on the host plane, or ``other``.

The device and host clocks of one trace agree to about a millisecond.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "window"
_FINGERPRINT = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals (ns)."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def module_name(name: str) -> str:
    return _FINGERPRINT.sub("", name)


class TraceReading:
    """What one trace says: window, busy seconds per chip, program times,
    idle gaps."""

    def __init__(self, window: Interval, busy: Dict[str, List[Interval]],
                 modules: Dict[str, float], spans: List[Tuple[str, float, float]]):
        self.window = window
        self.busy = busy
        self.modules = modules
        self.spans = spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips in the trace."""
        if not self.busy:
            return 0.0
        per_chip = [sum(e - s for s, e in iv) * 1e-9 for iv in self.busy.values()]
        return sum(per_chip) / len(per_chip)

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def top_programs(self, n: int = 10) -> List[list]:
        ranked = sorted(self.modules.items(), key=lambda kv: -kv[1])
        return [[name, secs] for name, secs in ranked[:n]]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The ``n`` longest gaps in the first chip's busy time, named by
        the harness span over each gap's midpoint."""
        if not self.busy:
            return []
        lo, hi = self.window
        iv = next(iter(self.busy.values()))
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = 0.5 * (s + e)
            name = "other"
            best = None
            for span, a, b in self.spans:
                if a <= mid <= b and (best is None or b - a < best):
                    name, best = span, b - a
            out.append([name, (e - s) * 1e-9])
        return out


def read_trace(path: str, span_names: Sequence[str]) -> TraceReading:
    """Reduce the trace at ``path``; ``span_names`` are the harness's host
    spans that may name a gap."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = defaultdict(list)
    modules_iv: List[Tuple[str, float, float]] = []
    spans: List[Tuple[str, float, float]] = []
    window = None
    first, last = None, None
    names = set(span_names)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops[plane.name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules_iv.append((module_name(ev.name), ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                    first = a if first is None else min(first, a)
                    last = b if last is None else max(last, b)
                    if ev.name == WINDOW_SPAN:
                        window = (a, b)
                    elif ev.name in names:
                        spans.append((ev.name, a, b))
    if window is None:
        window = (first or 0.0, last or 0.0)
    lo, hi = window
    busy = {dev: clip(union(iv), lo, hi) for dev, iv in ops.items()}
    modules: Dict[str, float] = defaultdict(float)
    for name, a, b in modules_iv:
        for s, e in clip([(a, b)], lo, hi):
            modules[name] += (e - s) * 1e-9
    return TraceReading(window, busy, dict(modules), spans)
