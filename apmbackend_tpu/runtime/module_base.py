"""Shared scaffolding for pipeline module processes.

Every reference stream module repeats the same boot litany — read config, set
the global logger, watch the config file, open its queues, install
SIGINT/SIGTERM handlers that snapshot state and drain, and listen for the
manager's ``requestGC`` IPC message (e.g. stream_calc_stats.js's main IIFE;
util_methods.js:463-467). :class:`ModuleRuntime` centralizes that litany so a
module main is just: construct, wire queues, loop.

Differences from the reference, by design:

- IPC: the manager's ``requestGC`` rides SIGUSR1 instead of a Node IPC channel
  (portable to detached processes; apm_manager.js:505-509 role).
- Exit: handlers run in LIFO order (resume-save before queue shutdown), and a
  second signal forces immediate exit.
"""

from __future__ import annotations

import gc
import os
import signal
import sys
import threading
import time
from collections import deque
from typing import Callable, List, Optional

from ..config import ConfigWatcher, default_config, load_config
from ..logging_util import get_logger
from ..transport.base import QueueManager
from ..transport.memory import MemoryBroker, MemoryChannel

CONFIG_ENV_VAR = "APM_CONFIG"


def make_queue_manager(config: dict, logger=None, *, broker: Optional[MemoryBroker] = None,
                       redis_module=None) -> QueueManager:
    """QueueManager with the backend named by ``transport.broker`` (falling
    back to the top-level ``brokerBackend`` for pre-ISSUE-15 configs).

    ``memory``: channels share one in-process :class:`MemoryBroker` (passed in
    for single-process pipelines, else created + pump-started here).
    ``amqp``: one pika connection per channel against ``amqpConnectionString``,
    mirroring the reference's one-connection-per-direction design
    (queue.js:73-78).
    ``redis``: one Redis Streams channel per direction (consumer groups =
    manual ack, XAUTOCLAIM = redelivery), pump-started; ``redis_module``
    injects the in-process fake for serverless tests.
    ``spool``: channels share one durable file-backed SpoolChannel fabric
    under ``transport.spoolDirectory``, pump-started.
    """
    from ..transport import effective_broker_backend

    backend = effective_broker_backend(config)
    transport_cfg = config.get("transport", {}) or {}
    if backend == "memory":
        shared = broker or MemoryBroker()
        if broker is None:
            shared.start_pump_thread()
        # queue depth/bytes gauges (rabbitmqctl-list_queues role as a
        # scrape); idempotent per broker object
        from ..obs.views import register_memory_broker

        register_memory_broker(shared)
        factory = lambda _qtype: MemoryChannel(shared)  # noqa: E731
    elif backend == "amqp":
        from ..transport.amqp import AmqpChannel

        conn_str = config.get("amqpConnectionString", "amqp://localhost:5672")
        prefetch = int(config.get("amqpPrefetchCount", 1000))
        factory = lambda qtype: AmqpChannel(  # noqa: E731
            conn_str, direction=qtype, logger=logger, prefetch_count=prefetch
        )
    elif backend == "redis":
        from ..transport.redis_streams import RedisStreamsChannel

        redis_cfg = config.get("redis", {}) or {}

        def factory(_qtype):
            ch = RedisStreamsChannel(
                redis_cfg.get("connectionString", "redis://localhost:6379/0"),
                redis_module=redis_module, logger=logger,
                group=redis_cfg.get("group", "apm"),
                stream_maxlen=redis_cfg.get("streamMaxlen", 100000),
                claim_idle_ms=redis_cfg.get("claimIdleMs", 5000),
                prefetch=redis_cfg.get("prefetchCount", 1000),
            )
            # the pump owns delivery, reconnect backoff, ack retry, AND
            # producer-side drain detection (drain is polled, not pushed)
            ch.start_pump_thread()
            return ch
    elif backend == "spool":
        from ..transport.spool import SpoolChannel

        shared_spool = SpoolChannel(transport_cfg.get("spoolDirectory", "spool/broker"))
        shared_spool.start_pump_thread()
        factory = lambda _qtype: shared_spool  # noqa: E731
    elif backend == "shmring":
        from ..transport.shmring import DEFAULT_RING_BYTES, ShmRingChannel

        def factory(_qtype):
            ch = ShmRingChannel(
                transport_cfg.get("shmRingDirectory", "spool/shmring"),
                ring_bytes=int(transport_cfg.get("shmRingBytes", DEFAULT_RING_BYTES)),
                logger=logger,
            )
            # drain (free space after a refusal) is polled off the mmap by
            # the pump, not pushed — producer-side channels need it too
            ch.start_pump_thread()
            return ch
    else:
        raise ValueError(f"Unknown brokerBackend: {backend!r}")
    qm = QueueManager(factory, int(config.get("statLogIntervalInSeconds", 60)), logger=logger,
                      transport_config=transport_cfg)
    return qm


class ModuleRuntime:
    """Boot + lifecycle for one module process."""

    def __init__(
        self,
        section: str,
        *,
        config_path: Optional[str] = None,
        config: Optional[dict] = None,
        broker: Optional[MemoryBroker] = None,
        install_signals: bool = True,
        console_log: bool = True,
    ):
        self.section = section
        self.config_path = config_path or os.environ.get(CONFIG_ENV_VAR)
        if config is not None:
            self.config = config
        elif self.config_path:
            self.config = load_config(self.config_path, exit_on_missing=True)
        else:
            self.config = default_config()
        self.module_config = self.config.get(section, {})
        prefix = self.module_config.get("logFilePrefix", section)
        log_dir = self.config.get("logDir")
        self.logger = get_logger(log_dir, prefix, console=console_log)
        self.qm = make_queue_manager(self.config, self.logger, broker=broker)
        # producer-buffer overflow → flight bundle (rate-limited in the
        # handler); registered before flight exists, gated inside
        self._last_overflow_dump = 0.0
        self.qm.on("overflow", self._on_producer_overflow)
        self._exit_handlers: List[Callable[[], None]] = []
        self._reload_handlers: List[Callable[[dict], None]] = []
        self._exiting = False
        self._stop = threading.Event()
        self._timers: List[threading.Thread] = []
        self.watcher: Optional[ConfigWatcher] = None
        if self.config_path:
            self.watcher = ConfigWatcher(
                self.config_path, self._on_config_change, logger=self.logger
            )
            self.watcher.start()
        if install_signals:
            self._install_signals()
        # profiling harness (§5.1 parity): SIGUSR2 heap snapshot, MemoryError
        # auto-dump, optional JAX profiler server on module_config.profilerPort
        from ..utils.profiling import Profiling

        prof_cfg = dict(self.module_config)
        prof_cfg.setdefault("heapSnapshotDir", log_dir or "logs")
        self.profiling = Profiling(prefix, prof_cfg, logger=self.logger)
        self.profiling.install(install_signal=install_signals)

        # telemetry plane (obs/): absorb this module's queue counters into
        # the process registry, and — when the module config names a
        # metricsPort (0 = ephemeral) — serve /metrics, /healthz, /profile
        # from a per-module exporter thread.
        self.telemetry = None
        self.flight = None
        self.store = None
        self.slo = None
        self._span_seen: set = set()
        self._span_order: deque = deque()
        self._decision_seen_total = 0
        # serializes sample passes: the timer's immediate first fire can
        # overlap a manual _self_sample() (tests, /query warmup) and the
        # span/decision dedup state is read-modify-write
        self._sample_lock = threading.Lock()
        obs_cfg = self.config.get("observability", {})
        if bool(obs_cfg.get("enabled", True)):
            from ..obs.views import register_queue_stats

            register_queue_stats(self.qm.queue_stats, section)
            # fleet shards share one config file: the supervisor hands each
            # child its own exporter port via APM_METRICS_PORT (manager
            # expand_module_settings), overriding the section's metricsPort
            metrics_port = os.environ.get(
                "APM_METRICS_PORT", self.module_config.get("metricsPort")
            )
            if metrics_port is not None:
                from ..obs.exporter import TelemetryServer

                self.telemetry = TelemetryServer(
                    port=int(metrics_port),
                    host=str(obs_cfg.get("metricsHost", "127.0.0.1")),
                    profile_dir=log_dir or "logs",
                    module=prefix,
                    logger=self.logger,
                )
                self.telemetry.add_health("process", self._process_health)
                self.telemetry.add_health("flow_control", self._flow_control_health)
                self.telemetry.start()
                # ephemeral-port discovery seam: a supervisor that asked for
                # port 0 (fleet shards) learns the bound port from this file
                port_file = os.environ.get("APM_METRICS_PORT_FILE")
                if port_file:
                    try:
                        with open(port_file, "w") as fh:
                            fh.write(f"{self.telemetry.port}\n")
                    except OSError as e:
                        self.logger.warning(f"metrics port file write failed: {e}")
            # distributed trace plane (obs/trace): configure the process
            # tracer in place — transport objects cache the reference, so
            # this is wiring-order independent. In single-process topologies
            # every runtime applies the same shared config; only the
            # exporter-owning runtime claims the module label.
            from ..obs import trace as obs_trace

            obs_trace.configure(
                sample_rate=int(obs_cfg.get("traceSampleRate", 64) or 0),
                ring_size=int(obs_cfg.get("traceRingSize", 512)),
                module=prefix if self.telemetry is not None else None,
            )
            # wall-clock attribution plane (obs/attrib): install the stage/
            # occupancy collector into the process registry (idempotent —
            # standalone runs four runtimes over one registry); like the
            # tracer, only the exporter-owning runtime claims the module
            # label. _self_sample persists the series into the store, so
            # /query can plot stage shares over time.
            from ..obs.views import register_attribution

            register_attribution(prefix if self.telemetry is not None else None)
            # crash flight recorder (obs/flight): bundles on degradation/
            # signals/exceptions plus the kill−9 journal+sentinel shadow
            flight_dir = obs_cfg.get("flightDir")
            if flight_dir:
                from ..obs import get_registry
                from ..obs.decisions import get_decisions
                from ..obs.flight import FlightRecorder, config_hash
                from ..obs.trace import get_tracer

                self.flight = FlightRecorder(
                    str(flight_dir),
                    prefix,
                    max_bundles=int(obs_cfg.get("flightMaxBundles", 16)),
                    logger=self.logger,
                )
                self.flight.add_source("config_hash", lambda: config_hash(self.config))
                self.flight.add_source("metrics", lambda: get_registry().render())
                self.flight.add_source("traces", lambda: get_tracer().ring.spans(n=128))
                self.flight.add_source("decisions", lambda: get_decisions().recent(64))
                self.flight.add_source("process_health", self._process_health)
                # where the wall went when the process died: per-stage
                # busy/blocked table + bottleneck verdict, and the shm-ring
                # header counters (a stuck ring is visible even after the
                # peer process is gone — the file persists)
                from ..obs.attrib import get_attrib as _get_attrib

                self.flight.add_source("attribution", lambda: _get_attrib().snapshot())
                self.flight.add_source("shmring", self._shmring_stats)
                # a leftover sentinel = the previous process died without a
                # clean shutdown (kill−9/OOM): promote its last journal NOW
                self.flight.recover_crash()
                self.flight.mark_alive()
                self.every(
                    max(0.05, float(obs_cfg.get("flightJournalSeconds", 5.0))),
                    self.flight.journal,
                    name="flight-journal",
                )
                if self.telemetry is not None:
                    self.telemetry.flight = self.flight
            # durable telemetry spine (obs/store, DESIGN.md §8.4): a
            # per-module store behind GET /query, fed by registry snapshots
            # every selfSampleSeconds (plus new spans/decisions); the SLO
            # engine evaluates burn rates over it and degrades /healthz
            # to 503 while any objective fast-burns.
            if self.telemetry is not None:
                sample_s = float(obs_cfg.get("selfSampleSeconds", 2.0) or 0.0)
                if sample_s > 0:
                    from ..obs.store import TimeSeriesStore, make_query_route

                    store_dir = obs_cfg.get("storeDir")
                    self.store = TimeSeriesStore(
                        str(store_dir) if store_dir else None,
                        retention_s=float(obs_cfg.get("storeRetentionSeconds", 900.0)),
                        logger=self.logger,
                    )
                    self.telemetry.add_route("/query", make_query_route(lambda: self.store))
                    self.every(sample_s, self._self_sample, name="self-sample")
                slo_cfg = self.config.get("slo", {})
                if self.store is not None and bool(slo_cfg.get("enabled", True)):
                    from ..obs.slo import SLOEngine

                    self.slo = SLOEngine.from_config(
                        self.store, self.config, logger=self.logger
                    )
                    self.telemetry.add_health("slo", self.slo.health)
                    self.every(
                        max(0.05, float(slo_cfg.get("evaluationIntervalSeconds", 10.0))),
                        self.slo.evaluate,
                        name="slo-eval",
                    )
                if self.flight is not None and self.store is not None:
                    self.flight.add_source("store_tail", lambda: self.store.tail(32))
                    if self.slo is not None:
                        self.flight.add_source("slo", lambda: self.slo.status())

    def _shmring_stats(self) -> dict:
        """Header counters of every ring file in the shm fabric directory —
        a read-only peek (transport.shmring.ring_stats), so the flight
        snapshot never creates rings or races a peer's init. Empty when
        the broker backend is not shmring or the directory is absent."""
        from ..transport import effective_broker_backend

        if effective_broker_backend(self.config) != "shmring":
            return {}
        from ..transport.shmring import ring_stats

        tcfg = self.config.get("transport", {}) or {}
        directory = tcfg.get("shmRingDirectory", "spool/shmring")
        try:
            names = sorted(os.listdir(directory))
        except OSError:
            return {}
        out = {}
        for fn in names:
            if fn.endswith(".ring"):
                st = ring_stats(os.path.join(directory, fn))
                if st is not None:
                    out[fn[: -len(".ring")]] = st
        return out

    def _self_sample(self) -> None:
        """Snapshot the process registry — plus spans/decisions not yet
        persisted — into the per-module store (the /query data feed). Runs
        on its own timer thread; passes are serialized under _sample_lock
        so a manual invocation racing the timer's immediate first fire
        can never double-persist against a stale seen-counter."""
        from ..obs import get_registry
        from ..obs.decisions import get_decisions
        from ..obs.trace import get_tracer

        store = self.store
        if store is None:
            return
        with self._sample_lock:
            now = time.time()
            store.ingest_registry(get_registry(), ts=now)
            fresh = []
            for sp in get_tracer().ring.spans(n=256):
                key = (sp.get("trace_id"), sp.get("name"), sp.get("start"))
                if key in self._span_seen:
                    continue
                self._span_seen.add(key)
                self._span_order.append(key)
                while len(self._span_order) > 4096:
                    self._span_seen.discard(self._span_order.popleft())
                fresh.append(sp)
            if fresh:
                store.append_spans(fresh)
            # one atomic (total, items) snapshot: a decision recorded after
            # it is counted next pass, never double-persisted against a
            # stale total. If more than the ring size arrived since the
            # last pass the overflow is already gone from the ring either
            # way — persist what survives and advance the seen-counter past
            # the loss.
            ring = get_decisions()
            total, items = ring.snapshot(512)
            new = total - self._decision_seen_total
            if new > 0:
                store.append_decisions(items[-new:] if new < len(items) else items)
                self._decision_seen_total = total
            store.compact(now)

    def _process_health(self) -> dict:
        """Baseline liveness every module reports: the process is serving,
        its RSS, and whether a JAX device is attached (import-light: jax is
        only queried if something already imported it)."""
        from ..utils.profiling import loaded_jax

        out = {"ok": True, "rss_mb": round(_rss_mb(), 1), "section": self.section}
        jax_mod = loaded_jax()
        if jax_mod is not None:
            try:
                devs = jax_mod.local_devices()
                out["devices"] = [str(d) for d in devs]
                out["ok"] = bool(devs)
            except Exception as e:
                out["devices_error"] = repr(e)
                out["ok"] = False
        return out

    def _flow_control_health(self) -> dict:
        """Producer pause-buffer pressure: /healthz degrades (503) once any
        producer buffer reaches ``producerBufferDegradedRatio`` of the cap —
        the page fires BEFORE eviction starts, while the operator can still
        add consumers or raise the cap."""
        transport_cfg = self.config.get("transport", {}) or {}
        cap = int(transport_cfg.get("producerBufferMaxLines", 0) or 0)
        ratio = float(transport_cfg.get("producerBufferDegradedRatio", 0.8) or 0.8)
        buffers = self.qm.producer_buffer_counts()
        worst = max(buffers.values(), default=0)
        degraded = cap > 0 and worst >= cap * ratio
        return {
            "ok": not degraded,
            "producer_buffer_lines": buffers,
            "cap": cap,
            "degraded_at": int(cap * ratio) if cap > 0 else None,
        }

    def _on_producer_overflow(self, queue_name: str, evicted: int) -> None:
        """A producer buffer blew past its cap: capture a flight bundle
        (rate-limited — a sustained overflow episode is one incident, not a
        bundle per write_line)."""
        if self.flight is None:
            return
        now = time.monotonic()
        if now - self._last_overflow_dump < 30.0:
            return
        self._last_overflow_dump = now
        self.flight.dump(f"producer-overflow-{queue_name}", force=True)

    # -- config hot reload (§5.6) --------------------------------------------
    def on_reload(self, handler: Callable[[dict], None]) -> None:
        self._reload_handlers.append(handler)

    def _on_config_change(self, new_config: dict) -> None:
        self.config = new_config
        self.module_config = new_config.get(self.section, {})
        self.qm.set_interval(int(new_config.get("statLogIntervalInSeconds", 60)))
        for handler in self._reload_handlers:
            try:
                handler(new_config)
            except Exception as e:
                self.logger.error(f"Config reload handler error: {e}")

    # -- lifecycle -----------------------------------------------------------
    def on_exit(self, handler: Callable[[], None]) -> None:
        """Handlers run LIFO on shutdown (state snapshot first, transport last)."""
        self._exit_handlers.append(handler)

    def _install_signals(self) -> None:
        def _term(signum, _frame):
            self.logger.info(f"Caught signal {signal.Signals(signum).name}")
            if self._exiting:
                os._exit(1)
            if self.flight is not None:
                # the triage bundle must land BEFORE exit handlers start
                # tearing state down (they may hang — that is what the
                # second-signal os._exit path is for)
                try:
                    self.flight.dump(f"signal_{signal.Signals(signum).name}", force=True)
                except Exception:
                    pass
            self.exit()

        def _gc(_signum, _frame):
            # requestGC analog (util_methods.js:398-417): full collection +
            # a log line with before/after RSS when available.
            before = _rss_mb()
            gc.collect()
            self.logger.info(f"Garbage collection requested: RSS {before:.1f} -> {_rss_mb():.1f} MB")

        signal.signal(signal.SIGINT, _term)
        signal.signal(signal.SIGTERM, _term)
        if hasattr(signal, "SIGUSR1"):
            signal.signal(signal.SIGUSR1, _gc)

    def every(self, seconds: float, fn: Callable[[], None], *, name: str = "timer", align: bool = False) -> None:
        """Run ``fn`` every ``seconds`` until shutdown; ``align`` starts on a
        wall-clock multiple (the reference's second-aligned recursions)."""

        def _loop():
            if align:
                self._stop.wait(seconds - (time.time() % seconds))
            while not self._stop.is_set():
                try:
                    fn()
                except Exception as e:
                    self.logger.error(f"{name} error: {e}")
                self._stop.wait(seconds)

        t = threading.Thread(target=_loop, daemon=True, name=name)
        t.start()
        self._timers.append(t)

    def run_forever(self) -> None:
        try:
            while not self._stop.is_set():
                self._stop.wait(3600)
        except KeyboardInterrupt:
            self.exit()

    def stop_timers(self) -> None:
        """Stop the interval timers, the queue-stats logger, and the config
        watcher WITHOUT running exit handlers or exiting the process — for
        embedders (standalone pipeline, tests) that tear runtimes down
        in-process. JOINS every timer thread (bounded) so no interval
        callback can fire into closed log streams after this returns."""
        self._stop.set()
        if self.watcher is not None:
            self.watcher.stop()
        if self.telemetry is not None:
            try:
                self.telemetry.stop()
            except Exception:
                pass
            self.telemetry = None
        try:  # QueueStats runs its own timer thread, not a runtime.every one
            self.qm.queue_stats.stop()
        except Exception:
            pass
        me = threading.current_thread()
        for t in self._timers:
            if t is not me and t.is_alive():
                t.join(timeout=5.0)
        if self.store is not None:
            try:  # timers are joined: no more appends race the close
                self.store.close()
            except Exception:
                pass
        if self.flight is not None:
            # an orderly teardown is not a crash: consume the alive sentinel
            # so the next boot does not promote this run's journal
            self.flight.mark_clean_exit()

    def exit(self, code: int = 0) -> None:
        if self._exiting:
            return
        self._exiting = True
        self.stop_timers()
        for handler in reversed(self._exit_handlers):
            try:
                handler()
            except Exception as e:
                self.logger.error(f"Exit handler error: {e}")
        try:
            self.qm.shutdown()
        except Exception as e:
            self.logger.error(f"qm.shutdown() error: {e}")
        self.logger.info("Exiting...")
        if threading.current_thread() is threading.main_thread():
            sys.exit(code)
        # sys.exit from a worker thread only kills that thread and the process
        # would report rc=0; the fail-fast paths (tail death) need the real
        # exit code for the supervisor's restart logic. Handlers already ran.
        os._exit(code)


def _rss_mb() -> float:
    try:
        with open("/proc/self/statm") as fh:
            pages = int(fh.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE") / (1024 * 1024)
    except (OSError, ValueError, IndexError):
        return 0.0
