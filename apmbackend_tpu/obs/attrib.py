"""Pipeline-wide wall-clock attribution: where does a second of wall go?

The telemetry plane answers "how fast" (throughput counters) and "how
long" (latency histograms); the tick tracer decomposes one tick. None of
them answer the ROADMAP's standing question — *which stage owns the
wall-clock* — so every bottleneck diagnosis (replay is parser-bound,
fleet e2e is tick-cadence-bound) had to be reconstructed by hand across
bench reports. This module makes that attribution a first-class,
queryable signal:

- :class:`StageClock` — per-stage busy / blocked-on-downstream / idle
  second accumulators. Cost discipline is the PR 2 rule: deltas are taken
  from ``time.perf_counter()`` values the call sites already have at
  existing sync boundaries (tick t0..t3, parser parse_ns, ring spin
  deadlines) — no new device syncs, no hot-path locks. Each clock is
  written by the one thread that owns its stage (the shm-ring SPSC
  discipline); readers take a snapshot of plain floats, so a torn read
  costs at most one in-flight delta, never a crash.
- :class:`Occupancy` — time-weighted occupancy for the buffered resources
  (producer pause buffer, worker intake ring, frame FIFO, shm ring):
  ``sample(level)`` integrates ``level`` over the time it was held, which
  generalizes the instantaneous ``apm_shmring_occupancy_bytes`` gauge
  into "how full was it *on average*, and at peak".
- :class:`AttributionPlane` — the process-wide table of clocks +
  occupancies, exported to the registry (``apm_stage_*_seconds_total``,
  ``apm_occupancy_*``) so the PR 12 TimeSeriesStore's self-sample
  persists stage shares for ``/query`` range plots, and served by the
  exporter's ``GET /attrib`` with a critical-path verdict.

The bottleneck estimator (:func:`estimate`): every stage contributes its
busy share and blocked share of the observation window; the wall the
instrumented stages do NOT account for is the pipeline waiting for the
next tick boundary to arrive in the stream (ticks fire on data labels —
``feed`` only ticks when a record's 10 s label advances), reported as the
implicit ``tick_cadence`` candidate. The verdict is the argmax share:
``{"bottleneck": "tick_cadence", "reason": "drain_wait 71% of window"}``
for a cadence-dominated fleet, ``parser_scan`` for a parser-bound replay.
bench_replay/bench_rolling certify both namings under reproducible
inputs, and bench_rolling's ON-vs-OFF A/B gates accounting overhead <2%.

Intervals on the profiler's clock: :meth:`StageClock.span` adds the busy
delta exactly as ``add_busy`` does and, in the same block, opens a
``jax.profiler.TraceAnnotation`` named by the stage, so a profiler trace
shows each stage's intervals on the host plane beside the device planes.
Spans sit at per-chunk / per-batch / per-tick / per-timer boundaries,
never per line or message. jax is used only if this process has already
imported it; ``obs/`` never imports it, and without it a span only
accumulates. :data:`SPAN_STAGES` is the vocabulary a trace reader looks
for. Counters (records, slots, bytes, ...) ride a clock as plain numbers
in its snapshot.

Kill switch: ``APM_NO_ATTRIB=1`` (or ``configure(enabled=False)``) makes
:meth:`AttributionPlane.clock` hand out a shared no-op clock — call
sites keep their single cached reference and pay one dead method call;
its spans neither accumulate nor annotate.
Stdlib + numpy-free like the rest of ``obs/``.
"""

from __future__ import annotations

import gc
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

from .registry import MetricsRegistry, Sample, get_registry

# canonical stage names (call sites may mint others; these keep the
# table, the docs, and the bench assertions in one vocabulary)
STAGE_TAILER_READ = "tailer_read"
STAGE_PARSER_SCAN = "parser_scan"
STAGE_FRAME_PACK = "frame_pack"
STAGE_TRANSPORT_SEND = "transport_send"
STAGE_TRANSPORT_PUMP = "transport_pump"
STAGE_SHMRING_PUSH = "shmring_push"
STAGE_SHMRING_POP = "shmring_pop"
STAGE_INTAKE_PUSH = "intake_push"
STAGE_WORKER_FEED = "worker_feed"
STAGE_TICK_DISPATCH = "tick_dispatch"
STAGE_TICK_REBUILD = "tick_rebuild"
STAGE_TICK_TX_DRAIN = "tick_tx_drain"
STAGE_TICK_EMIT = "tick_emit"  # counters: banded, signals (emitted rows)
STAGE_SINK_ABSORB = "sink_absorb"  # insert flushes (counters: rows, lines: rows from tx lines)
STAGE_PARSER_SWEEP = "parser_sweep"  # the cache-sweep timer (counters: released, failed, examined)
STAGE_STATE_SAVE = "state_save"  # resume/checkpoint writes (counter: bytes)
# resume/checkpoint reads at boot (counters: bytes, keys, valid_slots,
# load_ms, install_ms, derive_ms)
STAGE_STATE_RESTORE = "state_restore"
STAGE_INGEST_DISPATCH = "ingest_dispatch"  # host side of one ingest call (records, slots)
STAGE_GC = "gc"  # the garbage collector's collections

# the stages whose intervals reach the profiler (StageClock.span call sites);
# the per-line and per-message stages stay clock-only
SPAN_STAGES = (
    STAGE_PARSER_SCAN,
    STAGE_PARSER_SWEEP,
    STAGE_WORKER_FEED,
    STAGE_INGEST_DISPATCH,
    STAGE_TICK_DISPATCH,
    STAGE_TICK_REBUILD,
    STAGE_TICK_TX_DRAIN,
    STAGE_TICK_EMIT,
    STAGE_SINK_ABSORB,
    STAGE_STATE_SAVE,
    STAGE_STATE_RESTORE,
    STAGE_GC,
)

# the implicit candidate: wall no instrumented stage accounts for —
# waiting for the stream to reach the next tick boundary
CADENCE = "tick_cadence"


_annotation = None  # jax.profiler.TraceAnnotation once jax is loaded here


def _annotation_cls():
    """The profiler's annotation class if this process has imported jax,
    else None. Never imports jax (the obs import-light rule)."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


class _Span:
    """One busy interval of a clock: ``add_busy(perf_counter delta)`` on
    exit, inside a profiler annotation named by the stage while a trace
    is being recorded."""

    __slots__ = ("clock", "t0", "ann")

    def __init__(self, clock: "StageClock"):
        self.clock = clock
        self.ann = None

    def __enter__(self) -> "_Span":
        cls = _annotation_cls()
        if cls is not None and cls.is_enabled():
            self.ann = cls(self.clock.stage)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *_exc) -> bool:
        self.clock.add_busy(time.perf_counter() - self.t0)
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        return False


class _NullSpan:
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *_exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class StageClock:
    """Busy/blocked/idle accumulators for ONE stage, plus named counters.

    Single-writer: the thread that owns the stage adds deltas; plain
    float adds under the GIL, no lock on the hot path. ``enabled`` lets
    call sites skip even the perf_counter pair when the plane is off.
    """

    __slots__ = ("stage", "busy_s", "blocked_s", "idle_s", "events", "counts")

    enabled = True

    def __init__(self, stage: str):
        self.stage = stage
        self.busy_s = 0.0
        self.blocked_s = 0.0
        self.idle_s = 0.0
        self.events = 0
        self.counts: Dict[str, float] = {}

    def add_busy(self, dt: float) -> None:
        if dt > 0.0:
            self.busy_s += dt
            self.events += 1

    def span(self):
        """``with clock.span():`` — the block's wall time is one busy
        interval (as ``add_busy``), and a profiler interval named by the
        stage when a trace is recording."""
        return _Span(self)

    def count(self, name: str, n: float = 1) -> None:
        """Add ``n`` to the counter ``name`` (``n=0`` declares it)."""
        self.counts[name] = self.counts.get(name, 0) + n

    def add_blocked(self, dt: float) -> None:
        if dt > 0.0:
            self.blocked_s += dt

    def add_idle(self, dt: float) -> None:
        if dt > 0.0:
            self.idle_s += dt

    def snapshot(self) -> dict:
        return {
            "busy_s": self.busy_s,
            "blocked_s": self.blocked_s,
            "idle_s": self.idle_s,
            "events": self.events,
            **self.counts,
        }


class _SharedClock(StageClock):
    """A clock that several threads write (state saves from the engine's
    timer, the alerts' and the sink's shutdown): adds under a lock."""

    __slots__ = ("_lock",)

    def __init__(self, stage: str):
        super().__init__(stage)
        self._lock = threading.Lock()

    def add_busy(self, dt: float) -> None:
        with self._lock:
            super().add_busy(dt)

    def count(self, name: str, n: float = 1) -> None:
        with self._lock:
            super().count(name, n)


class _NullClock(StageClock):
    """The disabled plane's shared clock: same API, zero accumulation."""

    __slots__ = ()

    enabled = False

    def add_busy(self, dt: float) -> None:
        pass

    def span(self):
        return _NULL_SPAN

    def count(self, name: str, n: float = 1) -> None:
        pass

    def add_blocked(self, dt: float) -> None:
        pass

    def add_idle(self, dt: float) -> None:
        pass


NULL_CLOCK = _NullClock("_disabled")  # the disabled plane's clock, for call sites with none


class Occupancy:
    """Time-weighted occupancy of one bounded resource.

    ``sample(level)`` charges the PREVIOUS level for the time it was
    held; the average is the integral over elapsed time, so a buffer
    that spikes for 1 ms out of 10 s averages near zero instead of
    whatever the scrape happened to catch. Single-writer like
    :class:`StageClock`."""

    __slots__ = ("resource", "capacity", "_level", "_last", "_integral",
                 "peak", "_t0")

    enabled = True

    def __init__(self, resource: str, capacity: Optional[float] = None):
        self.resource = resource
        self.capacity = capacity
        self._level = 0.0
        self._t0 = self._last = time.perf_counter()
        self._integral = 0.0
        self.peak = 0.0

    def sample(self, level: float) -> None:
        now = time.perf_counter()
        self._integral += self._level * (now - self._last)
        self._last = now
        self._level = float(level)
        if level > self.peak:
            self.peak = float(level)

    def time_avg(self) -> float:
        now = time.perf_counter()
        elapsed = now - self._t0
        if elapsed <= 0.0:
            return 0.0
        return (self._integral + self._level * (now - self._last)) / elapsed

    def snapshot(self) -> dict:
        out = {
            "avg": self.time_avg(),
            "peak": self.peak,
            "level": self._level,
        }
        if self.capacity:
            out["capacity"] = self.capacity
            out["utilization"] = out["avg"] / self.capacity
        return out


class _NullOccupancy(Occupancy):
    __slots__ = ()

    enabled = False

    def sample(self, level: float) -> None:
        pass


_NULL_OCC = _NullOccupancy("_disabled")


def estimate(stages: Dict[str, dict], window_s: float) -> dict:
    """The critical-path verdict over a stage table.

    Every stage candidates twice — its busy share (it IS the work) and
    its blocked share (it is starved BY downstream; rendered as
    ``<stage>_wait``). The unaccounted wall candidates as the implicit
    ``tick_cadence``/``drain_wait`` (ticks fire on stream labels, so
    un-attributed wall is the pipeline waiting for the next boundary).
    Stages may run on parallel threads, so shares can sum past 1.0; the
    unaccounted remainder is clamped at zero, which only ever
    *understates* cadence wait — the conservative direction."""
    window_s = max(float(window_s), 1e-9)
    candidates = []  # (stage, mode, share)
    accounted = 0.0
    for stage, st in stages.items():
        busy = float(st.get("busy_s", 0.0))
        blocked = float(st.get("blocked_s", 0.0))
        accounted += busy + blocked
        candidates.append((stage, "busy", busy / window_s))
        if blocked > 0.0:
            candidates.append((stage, "blocked", blocked / window_s))
    cadence_share = max(0.0, 1.0 - accounted / window_s)
    candidates.append((CADENCE, "drain_wait", cadence_share))
    stage, mode, share = max(candidates, key=lambda c: c[2])
    if mode == "busy":
        reason = f"busy {share * 100.0:.0f}% of window"
    elif mode == "blocked":
        reason = f"{stage}_wait {share * 100.0:.0f}% of window"
    else:
        reason = f"drain_wait {share * 100.0:.0f}% of window"
    return {
        "bottleneck": stage,
        "mode": mode,
        "share": round(share, 4),
        "reason": reason,
        "verdict": f"bottleneck: {stage}, confidence: {reason}",
        "window_s": round(window_s, 3),
    }


class AttributionPlane:
    """The process-wide attribution table (one per process; see
    :func:`get_attrib`). Creation of clocks/occupancies is locked; the
    accumulators themselves are single-writer lock-free."""

    def __init__(self, module: str = "apm", enabled: Optional[bool] = None):
        if enabled is None:
            enabled = os.environ.get("APM_NO_ATTRIB", "") in ("", "0")
        self.module = module
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._clocks: Dict[str, StageClock] = {}  # guarded-by: _lock (creation; accumulation is single-writer)
        self._occ: Dict[str, Occupancy] = {}  # guarded-by: _lock (creation)
        self._t0 = time.perf_counter()
        self._wall0 = time.time()
        self._track_gc = False

    # -- wiring ---------------------------------------------------------------
    def configure(self, *, module: Optional[str] = None,
                  enabled: Optional[bool] = None) -> "AttributionPlane":
        if module is not None:
            self.module = module
        if enabled is not None:
            self.enabled = bool(enabled)
        return self

    def clock(self, stage: str, counters: Sequence[str] = (),
              shared: bool = False) -> StageClock:
        """Create-or-get the stage's clock, with ``counters`` declared at
        0 so they appear before the first count; ``shared`` for a stage
        that several threads write. The shared no-op when the plane is
        disabled (call sites cache the reference either way)."""
        if not self.enabled:
            return NULL_CLOCK
        with self._lock:
            clock = self._clocks.get(stage)
            if clock is None:
                clock = self._clocks[stage] = (_SharedClock if shared else StageClock)(stage)
            for name in counters:
                clock.counts.setdefault(name, 0)
            return clock

    def track_gc(self) -> None:
        """Time the garbage collector's collections on this plane's ``gc``
        clock (the process's one ``gc.callbacks`` hook, installed on first
        use). Kept across :meth:`reset`; nothing is recorded while the
        plane is off."""
        if not self.enabled:
            return
        with self._lock:
            if STAGE_GC not in self._clocks:
                self._clocks[STAGE_GC] = StageClock(STAGE_GC)
            self._track_gc = True
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    def occupancy(self, resource: str,
                  capacity: Optional[float] = None) -> Occupancy:
        if not self.enabled:
            return _NULL_OCC
        with self._lock:
            occ = self._occ.get(resource)
            if occ is None:
                occ = self._occ[resource] = Occupancy(resource, capacity)
            elif capacity is not None and occ.capacity is None:
                occ.capacity = capacity
            return occ

    def reset(self) -> None:
        """Restart the observation window (bench phase boundaries)."""
        with self._lock:
            self._clocks.clear()
            if self._track_gc:
                self._clocks[STAGE_GC] = StageClock(STAGE_GC)
            self._occ.clear()
            self._t0 = time.perf_counter()
            self._wall0 = time.time()

    def window_s(self) -> float:
        return time.perf_counter() - self._t0

    # -- views ----------------------------------------------------------------
    def stage_table(self) -> Dict[str, dict]:
        with self._lock:
            clocks = list(self._clocks.values())
        return {c.stage: c.snapshot() for c in clocks}

    def occupancy_table(self) -> Dict[str, dict]:
        with self._lock:
            occs = list(self._occ.values())
        return {o.resource: o.snapshot() for o in occs}

    def snapshot(self) -> dict:
        """The full attribution picture: the /attrib body, the flight
        recorder's ``attribution`` source, and the bench certification
        input — one shape everywhere."""
        window = self.window_s()
        stages = self.stage_table()
        body = {
            "module": self.module,
            "enabled": self.enabled,
            "window_s": round(window, 3),
            "window_start_unixtime": self._wall0,
            "stages": {
                s: dict(
                    st,
                    busy_share=round(st["busy_s"] / max(window, 1e-9), 4),
                    blocked_share=round(st["blocked_s"] / max(window, 1e-9), 4),
                )
                for s, st in stages.items()
            },
            "occupancy": self.occupancy_table(),
        }
        body["estimate"] = estimate(stages, window)
        return body

    def bottleneck(self) -> dict:
        return estimate(self.stage_table(), self.window_s())

    # -- registry export ------------------------------------------------------
    def collect(self) -> List[Sample]:
        """Scrape-time samples — the store's self-sample persists these,
        so ``/query`` can plot ``rate(apm_stage_busy_seconds_total[60s])``
        stage shares over time."""
        out: List[Sample] = []
        labels = {"module": self.module}
        for stage, st in self.stage_table().items():
            sl = dict(labels, stage=stage)
            out.append(Sample(
                "apm_stage_busy_seconds_total", sl, st["busy_s"], "counter",
                "Wall seconds the stage spent doing its own work",
            ))
            out.append(Sample(
                "apm_stage_blocked_seconds_total", sl, st["blocked_s"],
                "counter",
                "Wall seconds the stage spent blocked on downstream",
            ))
            out.append(Sample(
                "apm_stage_idle_seconds_total", sl, st["idle_s"], "counter",
                "Wall seconds the stage spent idle (no input pending)",
            ))
            out.append(Sample(
                "apm_stage_events_total", sl, st["events"], "counter",
                "Busy intervals the stage accumulated",
            ))
            for name, value in st.items():
                if name not in _CLOCK_FIELDS:
                    out.append(Sample(
                        "apm_stage_counter_total", dict(sl, counter=name), value,
                        "counter", "Items the stage counted, by counter",
                    ))
        for resource, oc in self.occupancy_table().items():
            rl = dict(labels, resource=resource)
            out.append(Sample(
                "apm_occupancy_avg", rl, oc["avg"], "gauge",
                "Time-weighted average occupancy of the buffered resource",
            ))
            out.append(Sample(
                "apm_occupancy_peak", rl, oc["peak"], "gauge",
                "Peak occupancy of the buffered resource",
            ))
            out.append(Sample(
                "apm_occupancy_level", rl, oc["level"], "gauge",
                "Most recently sampled occupancy of the buffered resource",
            ))
        return out

    _registered_into: Optional[int] = None

    def install(self, registry: Optional[MetricsRegistry] = None) -> None:
        """Idempotently register the collector (the views.py _MARK
        discipline: standalone mode builds several runtimes over one
        process registry — one collector, not four)."""
        reg = registry if registry is not None else get_registry()
        if self._registered_into == id(reg):
            return
        self._registered_into = id(reg)
        reg.add_collector(self.collect)


# -- the process-global plane -------------------------------------------------

_plane = AttributionPlane()
_CLOCK_FIELDS = ("busy_s", "blocked_s", "idle_s", "events")
_SHARES = ("busy_share", "blocked_share")  # added per stage by snapshot()
_gc_span: List[Optional[_Span]] = [None]  # the open collection (one at a time per process)


def _on_gc(phase: str, _info: dict) -> None:
    """``gc.callbacks`` hook: one span per collection, opened on ``start``
    and closed on ``stop`` on the collecting thread. Takes no lock: a
    collection can start while this thread holds the plane's lock."""
    if phase == "start":
        plane = _plane
        clock = plane._clocks.get(STAGE_GC) if plane.enabled else None
        if clock is not None:
            _gc_span[0] = clock.span().__enter__()
    else:
        span, _gc_span[0] = _gc_span[0], None
        if span is not None:
            span.__exit__(None, None, None)


def get_attrib() -> AttributionPlane:
    """The process-wide attribution plane every stage records into."""
    return _plane


def configure(**kwargs) -> AttributionPlane:
    """Configure the process plane in place (ModuleRuntime wiring; tests)."""
    return _plane.configure(**kwargs)


def set_attrib(plane: AttributionPlane) -> AttributionPlane:
    """Swap the process-global plane (test/bench isolation); returns the
    old one. Call sites cache clock references at construction, so a swap
    takes effect for components built AFTER it — the bench A/B pattern."""
    global _plane
    old, _plane = _plane, plane
    return old


def merge_snapshots(snapshots: List[dict]) -> dict:
    """Fleet-merge child /attrib bodies: stage seconds sum across
    children (stages run in parallel processes — the estimator's
    parallel-threads caveat already covers this), occupancy keeps each
    child's row under ``<module>:<resource>``, and the verdict is
    recomputed over the merged table with the widest child window."""
    stages: Dict[str, dict] = {}
    occupancy: Dict[str, dict] = {}
    window = 0.0
    children = []
    for snap in snapshots:
        if not snap:
            continue
        children.append(snap.get("module", "?"))
        window = max(window, float(snap.get("window_s", 0.0)))
        for stage, st in (snap.get("stages") or {}).items():
            agg = stages.setdefault(
                stage, {"busy_s": 0.0, "blocked_s": 0.0, "idle_s": 0.0,
                        "events": 0})
            agg["busy_s"] += float(st.get("busy_s", 0.0))
            agg["blocked_s"] += float(st.get("blocked_s", 0.0))
            agg["idle_s"] += float(st.get("idle_s", 0.0))
            agg["events"] += int(st.get("events", 0))
            for name, value in st.items():  # the clock's counters
                if name not in _CLOCK_FIELDS and name not in _SHARES \
                        and isinstance(value, (int, float)):
                    agg[name] = agg.get(name, 0) + value
        for resource, oc in (snap.get("occupancy") or {}).items():
            occupancy[f"{snap.get('module', '?')}:{resource}"] = oc
    body = {
        "children": children,
        "window_s": round(window, 3),
        "stages": stages,
        "occupancy": occupancy,
    }
    body["estimate"] = estimate(stages, window)
    return body


def make_attrib_route(plane_fn: Optional[Callable[[], AttributionPlane]] = None):
    """``GET /attrib`` route body for :meth:`TelemetryServer.add_route`."""
    import json

    def route(_query):
        plane = plane_fn() if plane_fn is not None else get_attrib()
        return 200, "application/json", json.dumps(
            plane.snapshot(), indent=1, default=repr)

    return route
