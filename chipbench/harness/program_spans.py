"""The program's own stage spans in a traced run, beside the device's busy
time: what the host was doing while the chip sat idle.

The program opens a profiler annotation named by its stage at each stage
boundary (the names in ``apmbackend_tpu.obs.attrib.SPAN_STAGES``; a program
without that tuple has no spans, and every function here then gives None).
``read_trace`` keeps the harness's spans only, so the program's are read
from the same file again:

- ``program_spans``: (stage, thread, start ns, end ns) of each host event
  named by a program stage, and the trace's window as ``read_trace`` finds it;
- ``unattributed_idle_share``: percent of the window in which the chip is
  idle and no program span is open on any host thread;
- ``idle_gaps_by_stage``: the longest idle gaps, each named by the program
  spans open over its midpoint (the innermost on each thread, joined with
  ``+``), or ``none``;
- ``ingest_device_ms``: device milliseconds of the ingest program per
  ``ingest_dispatch`` span that starts in the window;
- ``spans_for``: the spans of a reader's run context. A context that holds
  ``program_spans`` is used as it is; otherwise the traced run's file is
  found under ``run.py``'s work directory, and taken only if its window is
  the one of the context's ``TraceReading``.
"""

from __future__ import annotations

import glob
import os
from typing import List, Optional, Sequence, Tuple

from .trace import WINDOW_SPAN, TraceReading, clip, union

Span = Tuple[str, str, float, float]  # stage, host thread, start ns, end ns
INGEST_STAGE = "ingest_dispatch"
INGEST_MODULE = "jit_engine_ingest"
_CACHE = "_program_spans"


def program_stages() -> Optional[Tuple[str, ...]]:
    """The program's span vocabulary, or None for a program without one."""
    try:
        from apmbackend_tpu.obs.attrib import SPAN_STAGES
    except ImportError:
        return None
    return tuple(SPAN_STAGES)


def program_spans(path: str, names: Sequence[str]) -> Tuple[Tuple[float, float], List[Span]]:
    """The trace's window (as ``read_trace`` takes it) and every host event
    in it named in ``names``."""
    from jax.profiler import ProfileData

    names = set(names)
    spans: List[Span] = []
    window = None
    first = last = None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{i}:{line.name}"
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                first = a if first is None else min(first, a)
                last = b if last is None else max(last, b)
                if ev.name == WINDOW_SPAN:
                    window = (a, b)
                elif ev.name in names:
                    spans.append((ev.name, thread, a, b))
    if window is None:
        window = (first or 0.0, last or 0.0)
    return window, spans


def _trace_files() -> List[str]:
    from chipbench.run import WORK

    found = glob.glob(os.path.join(WORK, "trace", "**", "*.xplane.pb"), recursive=True)
    return sorted(found, key=os.path.getmtime, reverse=True)


def spans_for(run: dict) -> Optional[List[Span]]:
    """The program's spans in the run's traced window; None where the run
    has no trace or the program has no spans."""
    reading = run.get("trace")
    if reading is None:
        return None
    if "program_spans" in run:
        return run["program_spans"] or None
    if _CACHE not in run:
        run[_CACHE] = None
        names = program_stages()
        if names is not None:
            for path in _trace_files():
                window, spans = program_spans(path, names)
                if tuple(window) == tuple(reading.window):
                    run[_CACHE] = spans
                    break
    return run[_CACHE] or None


def _idle(busy: Sequence[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def unattributed_idle_share(reading: TraceReading, spans: Sequence[Span]) -> Optional[float]:
    """Percent of the window in which the chip is idle and no program span
    is open, averaged over the chips in the trace."""
    if not spans or not reading.busy or reading.window_s <= 0:
        return None
    lo, hi = reading.window
    covered = clip([(a, b) for _n, _t, a, b in spans], lo, hi)
    shares = []
    for busy in reading.busy.values():
        held = sum(e - s for s, e in union(list(busy) + covered))
        shares.append(1.0 - held / (hi - lo))
    return 100.0 * sum(shares) / len(shares)


def idle_gaps_by_stage(reading: TraceReading, spans: Sequence[Span], n: int = 10) -> List[list]:
    """The ``n`` longest gaps in the first chip's busy time, each named by
    the innermost program span on each thread open over its midpoint."""
    if not reading.busy:
        return []
    lo, hi = reading.window
    gaps = sorted(_idle(next(iter(reading.busy.values())), lo, hi), key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = 0.5 * (s + e)
        inner = {}
        for name, thread, a, b in spans:
            if a <= mid <= b and (thread not in inner or b - a < inner[thread][1]):
                inner[thread] = (name, b - a)
        names = sorted({name for name, _len in inner.values()})
        out.append(["+".join(names) or "none", (e - s) * 1e-9])
    return out


def ingest_device_ms(reading: TraceReading, spans: Sequence[Span]) -> Optional[float]:
    """Device milliseconds of the ingest program in the window, per chip,
    over the ``ingest_dispatch`` spans that start in the window."""
    lo, hi = reading.window
    dispatches = sum(1 for name, _t, a, _b in spans if name == INGEST_STAGE and lo <= a <= hi)
    device_s = sum(secs for name, secs in reading.modules.items() if name.startswith(INGEST_MODULE))
    if dispatches <= 0 or device_s <= 0 or not reading.busy:
        return None
    return 1000.0 * device_s / len(reading.busy) / dispatches
