"""Collector views absorbing pre-existing telemetry into the registry.

QueueStats/DBStats keep their reference-parity log-and-reset behavior
(the ``IN<q: n - OUT>q: m`` lines); these views read the CUMULATIVE
totals those classes now also maintain, so /metrics exports proper
monotonic counters while the legacy log lines stay byte-identical.
Registration helpers are idempotent per underlying object (standalone
mode builds four ModuleRuntimes over one broker in one process — the
depth gauges must not export four copies of the same series).
"""

from __future__ import annotations

from typing import Optional

from .registry import MetricsRegistry, Sample, get_registry

_MARK = "_apm_obs_registered"


def register_queue_stats(qs, module: str, registry: Optional[MetricsRegistry] = None) -> None:
    """QueueStats cumulative totals -> apm_queue_messages_total{queue,direction,module}."""
    if getattr(qs, _MARK, False):
        return
    setattr(qs, _MARK, True)
    reg = registry if registry is not None else get_registry()

    def collect():
        for name, ctype, total in qs.totals():
            yield Sample(
                "apm_queue_messages_total",
                {"queue": name, "direction": "in" if ctype == "c" else "out", "module": module},
                total,
                "counter",
                "Messages through each queue handle (cumulative; QueueStats view)",
            )

    reg.add_collector(collect)


def register_db_stats(db, module: str, registry: Optional[MetricsRegistry] = None) -> None:
    """DBStats cumulative totals -> rows-inserted / insert-time counters."""
    if getattr(db, _MARK, False):
        return
    setattr(db, _MARK, True)
    reg = registry if registry is not None else get_registry()

    def collect():
        rows, ms = db.totals()
        labels = {"module": module}
        yield Sample(
            "apm_db_rows_inserted_total", labels, rows, "counter",
            "Rows batch-inserted by the DB sink (cumulative; DBStats view)",
        )
        yield Sample(
            "apm_db_insert_seconds_total", labels, ms / 1000.0, "counter",
            "Wall time spent in DB inserts (cumulative; DBStats view)",
        )

    reg.add_collector(collect)


def register_memory_broker(broker, registry: Optional[MetricsRegistry] = None) -> None:
    """Live queue depth/bytes gauges over the in-process broker — the
    rabbitmqctl-list_queues role (apm_manager.js:429-453) as a scrape."""
    if getattr(broker, _MARK, False):
        return
    setattr(broker, _MARK, True)
    reg = registry if registry is not None else get_registry()

    def collect():
        for name in broker.queue_names():
            yield Sample(
                "apm_queue_depth", {"queue": name}, broker.queue_depth(name),
                "gauge", "Messages waiting in the queue (memory broker view)",
            )
            yield Sample(
                "apm_queue_memory_bytes", {"queue": name}, broker.queue_memory_bytes(name),
                "gauge", "Payload bytes waiting in the queue (memory broker view)",
            )

    reg.add_collector(collect)


def register_attribution(module: Optional[str], registry: Optional[MetricsRegistry] = None) -> None:
    """Export the process attribution plane (obs.attrib) into the registry:
    ``apm_stage_{busy,blocked,idle}_seconds_total`` + occupancy gauges,
    and time the garbage collector on its ``gc`` clock.
    Idempotent inside the plane itself (one collector per registry) — the
    standalone four-runtimes-one-registry topology registers once. A None
    ``module`` installs without claiming the label (non-exporter runtimes,
    mirroring the tracer's module rule)."""
    from .attrib import get_attrib

    plane = get_attrib()
    if module is not None:
        plane.configure(module=module)
    plane.install(registry)
    plane.track_gc()


def register_parser(parser, module: str, registry: Optional[MetricsRegistry] = None) -> None:
    """Correlation-parser stage counters (the ROADMAP "replay is
    parser-bound" quantification): line/record throughput, parse time,
    and correlation/account cache hit rates."""
    if getattr(parser, _MARK, False):
        return
    setattr(parser, _MARK, True)
    reg = registry if registry is not None else get_registry()
    labels = {"module": module}

    def collect():
        c = parser.counters
        yield Sample("apm_parser_lines_total", labels, c["lines_in"], "counter",
                     "Raw log lines fed to the correlation parser")
        yield Sample("apm_parser_tx_total", labels, c["tx_out"], "counter",
                     "Complete TxEntry records emitted by the parser")
        yield Sample("apm_parser_db_direct_total", labels, c["db_direct_out"], "counter",
                     "Records routed straight to the DB queue (non-Provider audit rows)")
        yield Sample("apm_parser_parse_seconds_total", labels, c["parse_ns"] / 1e9, "counter",
                     "Wall time inside TransactionParser.read_line/read_lines")
        yield Sample("apm_parser_native_lines_total", labels, c.get("native_lines", 0),
                     "counter",
                     "Lines processed by the native (C++) ingest fast path")
        yield Sample("apm_parser_prefilter_rejected_total", labels,
                     c.get("prefilter_rejected", 0), "counter",
                     "Lines dropped by the native marker pre-filter with zero Python work")
        yield Sample("apm_frames_emitted_total", labels,
                     c.get("frames_emitted", 0), "counter",
                     "APF1 frame batches emitted by the parser's frame mode")
        yield Sample("apm_frame_records_total", labels,
                     c.get("frame_records_out", 0), "counter",
                     "Records emitted via frame batches (no TxEntry, no on_record)")
        for cache, st in parser.cache_stats().items():
            cl = dict(labels, cache=cache)
            yield Sample("apm_parser_cache_hits_total", cl, st["hits"], "counter",
                         "Correlation cache hits (TTLCache view)")
            yield Sample("apm_parser_cache_misses_total", cl, st["misses"], "counter",
                         "Correlation cache misses (TTLCache view)")
            yield Sample("apm_parser_cache_keys", cl, st["keys"], "gauge",
                         "Live keys in the correlation cache")

    reg.add_collector(collect)
