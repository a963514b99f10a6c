"""The TPU pipeline worker process.

One process replaces three reference modules — stream_calc_stats,
stream_calc_z_score, stream_process_alerts — because the fused device step
(:mod:`apmbackend_tpu.pipeline`) runs all three stages in a single jit over
the whole service fleet. The process:

- consumes the ``transactions`` queue,
- feeds the :class:`PipelineDriver` (device micro-batching + 10 s ticks),
- emits ordered raw tx, FullStat passthrough, and AlertEntry rows to the
  ``db_insert`` queue (the reference's stream_calc_stats.js:364 heap drain,
  stream_process_alerts.js:618 passthrough, and :628 alert rows),
- optionally mirrors StatEntry / FullStatEntry lines onto the ``stats`` /
  ``z_score`` queues so reference-style per-stage consumers and the dequeue
  debug CLI keep working (the per-stage isolation seams of SURVEY.md §4),
- runs the alert email sender with interval doubling, Grafana render attach,
- snapshots device + alert state on an interval and on shutdown, restoring on
  boot (§5.4 semantics),
- honors pause/resume backpressure by cancelling/restarting consumption.

**Delivery modes** (``tpuEngine.deliveryMode``):

- ``atMostOnce`` (default, reference parity): the transport acks on receipt;
  anything in flight at a crash is lost, bounded by the resume cadence.
- ``atLeastOnce``: the worker drives an **epoch cycle** — feed → tick →
  checkpoint → ack. Messages are consumed manual-ack (tokens stay on the
  broker's unacked ledger), absorbed into the device state under the driver
  lock, and acked only AFTER the engine snapshot that contains their effects
  has been atomically written (ack-after-checkpoint). The snapshot carries a
  bounded dedup window of recently absorbed ``msg_id`` headers, so broker
  redeliveries after a crash (or duplicates injected in flight) are detected
  and skipped instead of double-counted: a restart is equivalent to the
  crash-free run for every fully-acked epoch, modulo the dedup window size.
  The native intake ring is bypassed in this mode (direct per-message feed
  keeps message↔state accounting exact — the ring's drop-oldest overflow
  escape hatch would break the token↔effect alignment the ack depends on).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

import numpy as np

from ..entries import EntryFactory
from ..integrations import EmailSender, GrafanaClient
from ..ops.alerts import AlertsManager
from ..pipeline import PipelineDriver
from ..transport import frames as _frames
from ..transport.memory import MemoryBroker


class _DedupWindow:
    """One queue's at-least-once dedup window + the incremental record a
    delta commit persists (added ids / evicted count since the last epoch).
    A fleet shard keeps one per owned partition queue — the window IS the
    unit the quiesced rebalance hands to the next owner (shardmodel.py);
    the single-queue worker is the one-entry case. All fields are
    guarded-by the owning worker's _driver_lock."""

    __slots__ = ("ids", "fifo", "added", "evicted", "deduped")

    def __init__(self):
        import collections

        self.ids: set = set()
        self.fifo: "collections.deque" = collections.deque()
        self.added: list = []
        self.evicted = 0
        self.deduped = 0  # redeliveries this window absorbed (persisted)


class WorkerApp:
    def __init__(self, runtime):
        self.runtime = runtime
        # The consumer thread (broker pump / AMQP) feeds the driver while the
        # resume-save timer thread flushes + snapshots it; PipelineDriver
        # itself is single-threaded by design, so serialize here.
        self._driver_lock = threading.RLock()
        self._closed = False
        config = runtime.config
        eng_cfg = config.get("tpuEngine", {})
        alerts_cfg = config.get("streamProcessAlerts", {})
        stats_cfg = config.get("streamCalcStats", {})
        logger = runtime.logger

        # -- delivery mode ---------------------------------------------------
        mode = str(eng_cfg.get("deliveryMode", "atMostOnce"))
        if mode not in ("atMostOnce", "atLeastOnce"):
            raise ValueError(
                f"tpuEngine.deliveryMode must be atMostOnce|atLeastOnce, got {mode!r}"
            )
        self._at_least_once = mode == "atLeastOnce"
        in_queue_name = stats_cfg.get("inQueue", "transactions")
        import collections

        # -- fleet identity (pod-scale sharding, DESIGN.md §10) --------------
        # fleet.shards > 0 turns this worker into ONE shard of a service-hash
        # partitioned fleet: it consumes the partition queues it owns
        # (`<inQueue>.p<K>`), each with its own dedup window, and its
        # checkpoint paths are {shard}-templated so N shards share one
        # config file with disjoint chains. Shard identity comes from the
        # APM_SHARD_ID env (the manager/harness stamp it per child) or
        # fleet.shardId for embedders.
        fleet_cfg = config.get("fleet", {}) or {}
        self._fleet_shards = int(fleet_cfg.get("shards", 0) or 0)
        sid = os.environ.get("APM_SHARD_ID")
        if sid is None:
            sid = fleet_cfg.get("shardId")
        self.shard_id: Optional[int] = int(sid) if sid is not None else None
        self._fleet = self._fleet_shards > 0 and self.shard_id is not None
        self._fleet_partitions = 0
        if self._fleet:
            if not self._at_least_once:
                raise ValueError(
                    "fleet.shards > 0 requires tpuEngine.deliveryMode: "
                    "atLeastOnce (the epoch cycle IS the sharded protocol)"
                )
            if not (0 <= self.shard_id < self._fleet_shards):
                raise ValueError(
                    f"shard id {self.shard_id} out of range for "
                    f"fleet.shards={self._fleet_shards}"
                )
            from ..parallel.fleet import resolve_partitions

            # P >= N: the keyspace grain is fleet.partitions (auto 4x
            # shards), NOT the shard count — the routing hash, the header
            # check, and boot ownership all use P
            self._fleet_partitions = resolve_partitions(
                self._fleet_shards, int(fleet_cfg.get("partitions", 0) or 0))
        self._partition_key = str(fleet_cfg.get("partitionKey", "service"))
        self._partition_base = in_queue_name
        self._epoch_stall_s = float(fleet_cfg.get("epochStallSeconds", 300.0) or 0.0)
        self._partition_mismatch_total = 0  # guarded-by: _driver_lock
        self._rebalances_total = 0  # guarded-by: _driver_lock
        self._last_epoch_commit = time.monotonic()  # guarded-by: _driver_lock

        # bounded dedup windows, one per consumed queue: ids of recently
        # ABSORBED messages (persisted with every checkpoint; membership =
        # "this message's effect is already in durable state, skip it").
        # Sized to cover the broker's redelivery span (<= prefetch) plus
        # injected duplicates. The single-queue worker keeps exactly one.
        self._dedup_max = int(eng_cfg.get("dedupWindowSize", 65536))
        self._windows: Dict[str, _DedupWindow] = {}  # guarded-by: _driver_lock
        self._epoch_tokens: list = []  # guarded-by: _driver_lock (absorbed, unacked delivery tokens)
        self._delivery_epoch = 0  # guarded-by: _driver_lock
        self._deduped_total = 0  # guarded-by: _driver_lock (apm_redelivered_deduped_total)
        # batched feed (ISSUE 4 satellite, ROADMAP PR-3 follow-up): accepted
        # deliveries buffer here and reach the engine as ONE bulk feed
        # (feed_csv_batch -> native decoder) instead of per-message
        # from_csv+feed — the direct path's per-message cost was a measured
        # -55% vs at-most-once. Token<->effect alignment is preserved
        # because every drain happens under the driver lock and save_state
        # drains BEFORE it checkpoints: a token only ever commits after its
        # line's effect is in the snapshot. Dedup-window ids are added at
        # ACCEPT time, which is safe for the same reason (the window is
        # only persisted by save_state, after the drain).
        self._alo_pending: list = []  # guarded-by: _driver_lock ((line|frame blob, ingest_ts|None, ctx, msg_id, queue))
        self._alo_batch = max(1, int(eng_cfg.get("deliveryBatchSize", 256)))
        self._alo_drain_s = float(eng_cfg.get("deliveryFeedMaxDelaySeconds", 0.25))
        # frame intake (transport.frameMode producers): packed APF1 batches
        # arrive as raw byte blobs — the consumer never unfolds them — and
        # go straight down the columnar path (driver.feed_frames). With
        # tpuEngine.feedFrames=false the worker decodes blobs back to lines
        # at FEED time instead (never at consume time, which would detach a
        # manual-ack batch from its single token).
        self._feed_frames = bool(eng_cfg.get("feedFrames", True))

        # protocol event log (analysis/protocol conformance): every
        # deliver/feed/checkpoint/ack/compact/recover step appended as one
        # JSONL line, replayed by the model checker's trace-conformance
        # tier as a path of the ALO + delta-chain models. Off (None) in
        # production unless an operator wants a protocol flight log.
        self._ev_fh = None
        self._ev_lock = threading.Lock()
        ev_path = self._shard_path(eng_cfg.get("protocolEventLog"))
        if ev_path:
            os.makedirs(os.path.dirname(os.path.abspath(ev_path)), exist_ok=True)
            self._ev_fh = open(ev_path, "a", encoding="utf-8")

        # -- checkpoint plane (full npz vs delta chain + failure policy) -----
        ck_mode = str(eng_cfg.get("checkpointMode", "full"))
        if ck_mode not in ("full", "delta"):
            raise ValueError(
                f"tpuEngine.checkpointMode must be full|delta, got {ck_mode!r}"
            )
        self._ckpt_mode = ck_mode
        self._ckpt_chain = None
        self._ckpt_compact_every = max(
            0, int(eng_cfg.get("checkpointCompactEveryEpochs", 64))
        )
        self._ckpt_last_compact = 0  # guarded-by: _driver_lock (chain epoch)
        # write-failure policy (ENOSPC/EIO graceful degradation): every
        # failed checkpoint write backs off with decorrelated jitter (the
        # AMQP reconnect _next_backoff shape — a fleet of workers on one
        # full disk must not hammer it in lockstep); after
        # checkpointWriteMaxRetries consecutive failures the worker enters
        # DEGRADED mode — flight bundle, operator alert, intake paused
        # (backpressure to the broker instead of a crash loop) — and keeps
        # retrying at the capped cadence until a write lands.
        import random as _random

        self._ckpt_max_retries = max(1, int(eng_cfg.get("checkpointWriteMaxRetries", 5)))
        self._ckpt_backoff_base = float(
            eng_cfg.get("checkpointWriteRetryBaseSeconds", 0.5)
        )
        self._ckpt_backoff_max = float(
            eng_cfg.get("checkpointWriteRetryMaxSeconds", 30.0)
        )
        self._ckpt_jitter = _random.Random()
        self._ckpt_fail_streak = 0  # guarded-by: _driver_lock
        self._ckpt_failures_total = 0  # guarded-by: _driver_lock
        self._ckpt_backoff = 0.0  # guarded-by: _driver_lock
        self._ckpt_retry_at: Optional[float] = None  # guarded-by: _driver_lock
        self._ckpt_degraded = False  # guarded-by: _driver_lock
        self._ckpt_paused_intake = False  # guarded-by: _driver_lock

        # -- outbound queues -------------------------------------------------
        qm = runtime.qm
        self.db_queue = qm.get_queue(config.get("dbInsertQueue", "db_insert"), "p")
        self.stats_queue = (
            qm.get_queue(stats_cfg.get("outQueue", "stats"), "p")
            if eng_cfg.get("emitStatsQueue")
            else None
        )
        zcfg = config.get("streamCalcZScore", {})
        self.zscore_queue = (
            qm.get_queue(zcfg.get("outQueue", "z_score"), "p")
            if eng_cfg.get("emitZScoreQueue")
            else None
        )

        # -- alert dispatch chain --------------------------------------------
        email_sender = None
        if alerts_cfg.get("emailsEnabled"):
            email_sender = EmailSender(
                alerts_cfg.get("fromEmail", "apm@localhost"),
                alerts_cfg.get("emailList", ""),
                logger=logger,
            )
        grafana_cfg = config.get("grafana", {})
        grafana = GrafanaClient(grafana_cfg, logger=logger) if grafana_cfg.get("grafanaURL") else None
        self.alerts_manager = AlertsManager(
            alerts_cfg, logger=logger, email_sender=email_sender, grafana=grafana
        )

        # -- operational alerts (manager-alert channel for engine health) ----
        # Chronic percentile-reservoir overflow is an operator problem (raise
        # samplesPerBucket), not a service anomaly, so it rides the manager's
        # batching alerter rather than the service AlertsManager.
        from ..manager.manager import ManagerAlerts

        self.ops_alerts = ManagerAlerts(
            config.get("applicationManager", {}), email_sender=email_sender, logger=logger
        )
        self._ops_alerts_started = False
        if email_sender is not None:
            # periodic batched dispatch (interval doubling); without a sender
            # the buffer just accrues under its cap until shutdown flush
            self.ops_alerts.start()
            self._ops_alerts_started = True
        self._overflow_alerted_ticks = 0

        # -- the device pipeline ---------------------------------------------
        from ..utils.compile_cache import use_compile_cache

        logger.info("XLA compile cache: %s", use_compile_cache() or "off")
        self.driver = PipelineDriver(
            config,
            alerts_manager=self.alerts_manager,
            on_stat=(lambda st: self.stats_queue.write_line(st.to_csv())) if self.stats_queue else None,
            on_fullstat_csv=self._on_fullstat_lines,
            on_ordered_csv=self.db_queue.write_line,
            on_overflow=self._on_overflow,
            logger=logger,
            micro_batch_size=int(eng_cfg.get("microBatchSize", 65536)),
        )
        # operability: which per-tick executor this worker resolved to
        # (tpuEngine.tickExecutor / state-size auto gate) and where the
        # staggered rebuild runs — the first thing to check when tick
        # latency looks wrong on a deployment
        logger.info(
            "Engine executor: %s (staggered rebuild: %s, async_emission=%s)",
            self.driver._step.kind,
            "integrated in tick program"
            if self.driver._step.rebuild_integrated
            else "separate scheduler",
            self.driver._async_emission,
        )

        # -- native intake ring ----------------------------------------------
        # The broker consumer thread pushes raw lines into the C++ SPSC ring;
        # a dedicated device-loop thread pops micro-batches and feeds the
        # driver via the bulk CSV path — the boundary the reference crosses
        # with RabbitMQ deliveries into consumeMsg
        # (stream_parse_transactions.js:902-975 fan-in scale). Ring-full
        # blocks the broker thread briefly = natural backpressure. Disable
        # with tpuEngine.useNativeRing=false; degrades to direct feed when
        # the native build is unavailable.
        self._ring = None
        self._ring_thread: Optional[threading.Thread] = None
        self._ring_stop = threading.Event()
        self._ring_pushed = 0  # lines accepted by _consume (single writer thread)
        self._ring_fed = 0  # lines handed to the driver (single device thread)
        # ring-full escape hatch: the broker delivery thread must not block
        # unboundedly (an AMQP consumer that stops pumping past the heartbeat
        # timeout gets its connection dropped). After a bounded spin, lines
        # overflow into this capped FIFO, drained by the device loop ahead of
        # newer ring entries; beyond the cap, drop-oldest + count.
        import collections

        self._overflow: collections.deque = collections.deque()  # guarded-by: _overflow_lock
        self._overflow_lock = threading.Lock()
        # packed-frame side intake (at-most-once + frameMode): frame blobs
        # cannot ride the byte ring (their lines region embeds the ring's
        # record separator), so they queue here — bounded by the same record
        # cap as the overflow FIFO — and the device loop drains them with
        # one feed_frames per blob, ahead of newer ring entries.
        self._frame_pending: collections.deque = collections.deque()  # guarded-by: _frame_lock ((blob, n_records))
        self._frame_pending_records = 0  # guarded-by: _frame_lock
        self._frame_lock = threading.Lock()
        # transport ingest stamps (header ingest_ts) of consumed-but-not-yet-
        # fed lines, FIFO like the ring: handed to the driver at FEED time so
        # an emission only ever claims stamps of lines actually in flight to
        # the device (a consume-time handoff let the first tick of a bulk
        # replay claim — and lose — every stamp while the ring still held
        # the lines). deque append/popleft are thread-safe (pump thread
        # appends, device loop pops).
        self._intake_ts_fifo: collections.deque = collections.deque()
        # sampled-trace handoff (obs/trace): trace contexts of consumed-but-
        # not-yet-fed SAMPLED messages, tagged with their consume sequence
        # (= _ring_pushed at accept) so the feed that absorbs line N also
        # registers every trace with seq <= N on the driver. Only 1/rate
        # messages ever enter this FIFO; unsampled traffic pays one dict.get.
        self._trace_fifo: collections.deque = collections.deque()
        self._overflow_max = int(eng_cfg.get("intakeOverflowMaxLines", 200_000))
        self.intake_dropped = 0
        self._ring_spin_s = float(eng_cfg.get("ringFullMaxBlockSeconds", 2.0))
        # wall-clock attribution (obs.attrib): intake-side stage clocks +
        # time-weighted occupancy of the ring-adjacent FIFOs. Plain float
        # adds on the owning threads at existing boundaries — no locks, no
        # device syncs (the PR 2 rule).
        from ..obs.attrib import STAGE_INTAKE_PUSH, STAGE_WORKER_FEED, get_attrib

        _att = get_attrib()
        self._att_feed = _att.clock(STAGE_WORKER_FEED)
        self._att_push = _att.clock(STAGE_INTAKE_PUSH)
        self._att_frame_occ = _att.occupancy(
            "frame_fifo_records", capacity=self._overflow_max
        )
        self._att_overflow_occ = _att.occupancy(
            "intake_overflow_lines", capacity=self._overflow_max
        )
        if self._at_least_once:
            # exact token<->effect accounting requires the direct feed path:
            # the ring batches lines detached from their delivery tokens and
            # its overflow cap drops oldest lines, either of which would let
            # an ack cover a message whose effect never reached the state
            logger.info(
                "Delivery mode atLeastOnce: native intake ring bypassed "
                "(direct per-message feed; epoch ack-after-checkpoint active)"
            )
        elif eng_cfg.get("useNativeRing", True):
            try:
                from ..native import LineRing

                self._ring = LineRing(int(eng_cfg.get("ringBytes", 1 << 22)))
            except Exception as e:
                logger.info(f"Native intake ring unavailable (direct feed): {e}")
        if self._ring is not None:
            self._ring_thread = threading.Thread(
                target=self._ring_loop, name="device-loop", daemon=True
            )
            self._ring_thread.start()

        # -- resume ----------------------------------------------------------
        self.engine_resume = self._shard_path(eng_cfg.get("resumeFileFullPath"))
        self.alerts_resume = self._shard_path(
            alerts_cfg.get("alertsResumeFileFullPath")
        )
        if self._ckpt_mode == "delta":
            from ..deltachain import CheckpointWriteError, DeltaChain

            chain_dir = self._shard_path(
                eng_cfg.get("checkpointChainDir") or "save/tpu_engine.chain"
            )
            self._ckpt_chain = DeltaChain(
                chain_dir,
                fsync=bool(eng_cfg.get("checkpointFsync", True)),
                logger=logger,
            )
            if self.driver.load_resume_chain(self._ckpt_chain):
                logger.info(
                    f"Engine state resumed from delta chain {chain_dir} "
                    f"(epoch {self._ckpt_chain.tail_epoch})"
                )
                self._seed_delivery()
            else:
                # fresh chain: the initial base IS the first committed epoch
                # boundary (an empty engine) — written before any ack can
                # happen. A failing disk at boot defers to the epoch commit
                # path's retry/degradation machinery.
                try:
                    self._ckpt_chain.initialize(
                        self.driver._capture_resume_arrays(None), epoch=0
                    )
                except CheckpointWriteError as e:
                    logger.error(f"Checkpoint chain initialize failed (will retry): {e}")
            self._ckpt_last_compact = self._ckpt_chain.tail_epoch
            self.driver.enable_delta_capture()
        elif self.engine_resume and self.driver.load_resume(self.engine_resume):
            logger.info(f"Engine state resumed from {self.engine_resume}")
            self._seed_delivery()
        if self.alerts_resume:
            self.alerts_manager.load_resume(self.alerts_resume)
        # conformance: the boot boundary — what epoch the durable state
        # restored to (0 = fresh) and, in delta mode, the chain position
        self._emit_event(
            "recover",
            epoch=self._delivery_epoch,
            chain_epoch=(self._ckpt_chain.tail_epoch
                         if self._ckpt_chain is not None else None),
            mode=self._ckpt_mode,
            window=self._window_total_locked(),
        )

        # float + floor: the chaos tier runs sub-second epoch cadences, and
        # int() would truncate 0.4 to a zero-interval busy loop
        save_s = max(0.05, float(stats_cfg.get("resumeFileSaveFrequencyInSeconds", 60)))
        runtime.every(save_s, self.save_state, name="resume-save")
        if self._at_least_once:
            # bound the emission latency the feed batching introduces:
            # sub-batch-size trickles still reach the engine on this cadence
            # (epoch COMMITS stay on the resume-save cadence)
            runtime.every(
                max(0.05, self._alo_drain_s), self.drain_delivery_pending,
                name="delivery-feed",
            )

        # interval-aligned intake counters, same style as QueueStats/DBStats
        # lines (§5.5 observability): the first place a wedged device loop or
        # chronic overflow shows up
        stat_s = int(config.get("statLogIntervalInSeconds", 60))
        runtime.every(stat_s, self._log_intake_stats, name="intake-stats")

        # HBM watchdog — the device-side analog of the manager's host-RSS
        # watchdog (apm_manager.js:486-509 role): the engine state lives on
        # the chip, so capacity growth or a lag/config change can exhaust
        # device memory long before host RSS moves. Telemetry every stats
        # interval; a rate-limited manager alert past the alarm fraction.
        self._hbm_alarm_fraction = float(eng_cfg.get("deviceMemoryAlarmFraction", 0.9))
        self._hbm_alerted = False
        self.hbm_bytes_in_use = 0
        self.hbm_bytes_limit = 0
        self._device_memory_stats = self._real_device_memory_stats  # test seam
        runtime.every(stat_s, self._check_device_memory, name="hbm-watchdog")

        # -- intake ----------------------------------------------------------
        # One consumer per owned queue. Non-fleet: the single in-queue.
        # Fleet: one partition queue per owned partition — ownership is
        # whatever the restored delivery tree says (a shard that handed a
        # partition away and restarted must NOT re-own it), defaulting to
        # the identity partition on a fresh boot.
        self._factory = EntryFactory()
        self.in_queues: Dict[str, object] = {}
        if self._fleet:
            for p in sorted(self._initial_partitions()):
                self._open_partition_queue(p)
        else:
            if self._at_least_once:
                with self._driver_lock:
                    self._windows.setdefault(in_queue_name, _DedupWindow())
            consumer = qm.get_queue(
                in_queue_name, "c", self._make_consume_cb(in_queue_name),
                manual_ack=self._at_least_once,
            )
            # frame batches reach _consume as raw blobs (no transport-side
            # unfold): the worker owns the bulk decode path
            consumer.frames_aware = True
            self.in_queues[in_queue_name] = consumer
        # primary queue handle (ack fan-in + single-queue compatibility)
        self.in_queue = next(iter(self.in_queues.values()), None)
        self._consume_enabled = bool(stats_cfg.get("consumeQueue", True))
        if self._consume_enabled:
            self._start_all_consume()
        qm.on("pause", self._stop_all_consume)
        qm.on("resume", lambda: self._start_all_consume() if self._consume_enabled else None)

        # -- alert sender recursion (stream_process_alerts.js:269-333) -------
        self._alert_timer: Optional[threading.Timer] = None
        self._schedule_alert_send(float(alerts_cfg.get("alertCollectionIntervalInSeconds", 60)))

        runtime.on_reload(self._apply_config)
        runtime.on_exit(self.shutdown)

        # -- telemetry -------------------------------------------------------
        # intake/HBM counters as a scrape view, and the engine healthz
        # section (tick liveness, emission backlog, device presence) on the
        # module exporter when one is configured. Collector registration is
        # gated on an exporter existing (own runtime's, or the lead's in
        # single-process standalone mode) so short-lived test pipelines do
        # not accumulate dead collectors in the process registry.
        from ..obs import get_registry, telemetry_active

        if getattr(runtime, "telemetry", None) is not None or telemetry_active():
            get_registry().add_collector(self._collect_metrics)
        if getattr(runtime, "telemetry", None) is not None:
            runtime.telemetry.add_health("engine", self._health)
        # -- durable control channel (fleet.controlDir) ----------------------
        # The rebalance controller's way into a SUPERVISED worker: the same
        # seq-numbered request/done file protocol the fleet harness drives
        # (a request survives kill -9 of either side; a restarted worker
        # re-executes the pending seq). The harness child (_shard_main)
        # polls inline instead, so controlDir stays None there.
        self._ctl_dir = (str(fleet_cfg.get("controlDir"))
                         if self._fleet and fleet_cfg.get("controlDir")
                         else None)
        if self._ctl_dir:
            os.makedirs(self._ctl_dir, exist_ok=True)
            self._ctl_path = os.path.join(
                self._ctl_dir, f"shard{self.shard_id}.ctl.json")
            self._ctl_done_path = self._ctl_path + ".done"
            self._ctl_last = self._read_ctl_seq(self._ctl_done_path)
            runtime.every(0.1, self._poll_control_file, name="fleet-ctl")

        flight = getattr(runtime, "flight", None)
        if flight is not None:
            # worker-specific flight-recorder sources: the tick-span ring
            # (where did the final ticks' time go), the engine healthz
            # section (backlog depths, delivery state, executor identity)
            flight.add_source(
                "tick_spans",
                lambda: self.driver._tracer.recent(64)
                if self.driver._tracer is not None else [],
            )
            flight.add_source("engine_health", self._health)

    def _emit_event(self, ev: str, **fields) -> None:
        """Append one protocol event (JSONL) — the trace-conformance feed.
        Failures never touch the protocol itself (best-effort log)."""
        fh = self._ev_fh
        if fh is None:
            return
        import json as _json

        fields["ev"] = ev
        fields["ts"] = time.time()
        if self._fleet:
            fields.setdefault("shard", self.shard_id)
        try:
            line = _json.dumps(fields, separators=(",", ":"))
            with self._ev_lock:
                fh.write(line + "\n")
                fh.flush()
        except Exception:
            pass

    # -- fleet plumbing ------------------------------------------------------
    def _shard_path(self, path):
        """``{shard}``-template a configured path with this worker's shard
        id, so N shards of one shared config get disjoint chains/resumes."""
        if path and self.shard_id is not None:
            return str(path).replace("{shard}", str(self.shard_id))
        return path

    def _queue_partition(self, qname: str) -> Optional[int]:
        from ..parallel.fleet import parse_partition

        return parse_partition(qname, self._partition_base)

    def _partition_pred(self, p: int):
        """(server, service) -> bool for rows routed to partition ``p``
        under the configured key — the SAME stable hash the producer-side
        partitioner routes by (routing discipline keeps per-shard dedup
        windows sufficient, shardmodel fleet-exactly-once)."""
        from ..parallel.fleet import service_partition

        key_is_service = self._partition_key != "server"
        n_parts = self._fleet_partitions

        def pred(server: str, service: str) -> bool:
            return service_partition(
                service if key_is_service else server, n_parts
            ) == p

        return pred

    def _make_consume_cb(self, qname: str):
        def cb(line, headers=None, token=None):
            self._consume(line, headers, token, qname)

        return cb

    def _open_partition_queue(self, p: int):
        from ..parallel.fleet import partition_queue

        qname = partition_queue(self._partition_base, p)
        with self._driver_lock:
            if qname not in self._windows:
                self._windows[qname] = _DedupWindow()
        consumer = self.runtime.qm.get_queue(
            qname, "c", self._make_consume_cb(qname), manual_ack=True
        )
        consumer.frames_aware = True
        self.in_queues[qname] = consumer
        return consumer

    def _initial_partitions(self) -> set:
        """Partitions this shard owns at boot: whatever queues the restored
        delivery tree carries (ownership rides the checkpoint — a released
        partition must stay released across a crash), or the striped set
        ``{p : p % N == shard_id}`` on a fresh boot (no delivery state ever
        committed) — the shardmodel initial pmap."""
        if self.driver.delivery_state is None:
            return {p for p in range(self._fleet_partitions)
                    if p % self._fleet_shards == self.shard_id}
        with self._driver_lock:
            owned = {
                self._queue_partition(q) for q in self._windows
            } - {None}
        return owned

    def _stop_all_consume(self) -> None:
        for q in list(getattr(self, "in_queues", {}).values()):
            q.stop_consume()

    def _start_all_consume(self) -> None:
        for q in list(getattr(self, "in_queues", {}).values()):
            q.start_consume()

    # apm: holds(_driver_lock): every caller acquires it (boot recover event, healthz, metrics)
    def _window_total_locked(self) -> int:
        return sum(len(w.fifo) for w in self._windows.values())

    @property
    def _dedup_fifo(self):
        """Primary queue's dedup FIFO — the single-queue view tests and the
        chaos harness predate the per-queue windows with."""
        q = self.in_queue.queue_name if self.in_queue is not None \
            else self._partition_base
        # apm: allow(lock-guard): read-only compatibility view for single-threaded test probes
        return self._windows.setdefault(q, _DedupWindow()).fifo

    @property
    def _dedup_set(self):
        q = self.in_queue.queue_name if self.in_queue is not None \
            else self._partition_base
        # apm: allow(lock-guard): read-only compatibility view for single-threaded test probes
        return self._windows.setdefault(q, _DedupWindow()).ids

    def _seed_delivery(self) -> None:
        """Seed the per-queue dedup windows / epoch watermark from a
        restored snapshot or chain: redeliveries of messages the checkpoint
        already absorbed are skipped. In fleet mode the set of restored
        queue records IS the shard's partition ownership."""
        dstate = self.driver.delivery_state or {}
        if not (self._at_least_once and dstate):
            return
        with self._driver_lock:  # boot wiring, but cheap to be rigorous
            epoch = 0
            deduped = 0
            for qname, rec in dstate.items():
                if self._fleet and self._queue_partition(qname) is None:
                    continue  # foreign record (e.g. pre-fleet snapshot)
                if not self._fleet and qname != self._partition_base:
                    continue  # another queue's record: not ours to consume
                w = self._windows.setdefault(qname, _DedupWindow())
                for mid in rec.get("dedup", []):
                    if mid not in w.ids:
                        w.ids.add(mid)
                        w.fifo.append(mid)
                w.deduped = int(rec.get("deduped_total", 0))
                epoch = max(epoch, int(rec.get("epoch", 0)))
                deduped += w.deduped
            self._delivery_epoch = epoch
            self._deduped_total = deduped
            n_window = self._window_total_locked()
            n_queues = len(self._windows)
        self.runtime.logger.info(
            f"Delivery state resumed: epoch {epoch}, dedup window {n_window} "
            f"ids across {n_queues} queue(s)"
        )

    def _collect_metrics(self):
        from ..obs import Sample

        yield Sample("apm_intake_pushed_total", {}, self._ring_pushed, "counter",
                     "Lines accepted from the broker into the intake path")
        yield Sample("apm_intake_fed_total", {}, self._ring_fed, "counter",
                     "Lines handed to the device driver")
        yield Sample("apm_intake_dropped_total", {}, self.intake_dropped, "counter",
                     "Lines dropped past the overflow cap (device loop stalled)")
        yield Sample("apm_intake_ring_bytes", {},
                     self._ring.used_bytes if self._ring is not None else 0,
                     "gauge", "Bytes buffered in the native intake ring")
        with self._overflow_lock:
            overflow_lines = len(self._overflow)
        yield Sample("apm_intake_overflow_lines", {}, overflow_lines, "gauge",
                     "Lines parked in the ring-full overflow FIFO")
        yield Sample("apm_hbm_bytes_in_use", {}, self.hbm_bytes_in_use, "gauge",
                     "Device memory in use (HBM watchdog view)")
        yield Sample("apm_hbm_bytes_limit", {}, self.hbm_bytes_limit, "gauge",
                     "Device memory limit (HBM watchdog view)")
        with self._driver_lock:
            ck_failures = self._ckpt_failures_total
            ck_degraded = self._ckpt_degraded
        yield Sample("apm_checkpoint_write_failures_total", {}, ck_failures,
                     "counter", "Checkpoint writes that failed (ENOSPC/EIO/...)")
        yield Sample("apm_checkpoint_degraded", {}, int(ck_degraded), "gauge",
                     "1 while persistent checkpoint failures keep intake paused")
        if self._ckpt_chain is not None:
            yield Sample("apm_checkpoint_chain_epoch", {},
                         self._ckpt_chain.tail_epoch, "gauge",
                         "Last committed delta-chain epoch")
            yield Sample("apm_checkpoint_delta_last_bytes", {},
                         self._ckpt_chain.last_delta_bytes, "gauge",
                         "Size of the most recent delta segment")
            yield Sample("apm_checkpoint_compactions_total", {},
                         self._ckpt_chain.compactions, "counter",
                         "Delta-chain full-snapshot compactions completed")
        if self._at_least_once:
            # consistent snapshot: the scrape must not interleave with an
            # epoch commit swapping the token list (RLock, scrape cadence).
            # In fleet mode every delivery/epoch series carries the
            # apm_shard_id label so the manager /fleet plane can pivot the
            # whole fleet per shard (DESIGN.md §8/§10).
            lbl = {"apm_shard_id": str(self.shard_id)} if self._fleet else {}
            with self._driver_lock:
                epoch = self._delivery_epoch
                deduped = self._deduped_total
                unacked = len(self._epoch_tokens)
                pending = len(self._alo_pending)
                window = self._window_total_locked()
                per_queue = {q: len(w.fifo) for q, w in self._windows.items()}
                mismatches = self._partition_mismatch_total
                rebalances = self._rebalances_total
                epoch_age = time.monotonic() - self._last_epoch_commit
            yield Sample("apm_delivery_epoch", lbl, epoch, "gauge",
                         "At-least-once epoch watermark (checkpoints committed)")
            yield Sample("apm_redelivered_deduped_total", lbl, deduped,
                         "counter",
                         "Redelivered/duplicate messages skipped by the dedup window")
            yield Sample("apm_delivery_unacked", lbl, unacked, "gauge",
                         "Absorbed-but-unacked deliveries in the open epoch")
            yield Sample("apm_delivery_pending_feed", lbl, pending,
                         "gauge",
                         "Accepted deliveries buffered for the next bulk feed")
            for q, n in per_queue.items():
                yield Sample("apm_delivery_dedup_window", dict(lbl, queue=q),
                             n, "gauge",
                             "Dedup-window occupancy (ids) per consumed queue")
            if self._fleet:
                yield Sample("apm_delivery_epoch_age_seconds", lbl,
                             epoch_age, "gauge",
                             "Seconds since the last committed epoch (stall lag)")
                yield Sample("apm_fleet_partition_mismatch_total", lbl,
                             mismatches, "counter",
                             "Deliveries whose partition header contradicted their queue (rejected)")
                yield Sample("apm_shard_rebalances_total", lbl, rebalances,
                             "counter",
                             "Partition handoffs (release + adopt) this shard completed")
                yield Sample("apm_shard_owned_partitions", lbl,
                             len(per_queue), "gauge",
                             "Partition queues this shard currently owns")
                # per-partition backlog: the rebalance controller's input
                # signal (rebalancer.observe_fleet parses exactly this
                # series to build its load view + ownership attribution)
                for qname, consumer in list(self.in_queues.items()):
                    p = self._queue_partition(qname)
                    if p is None:
                        continue
                    lag_fn = getattr(
                        getattr(consumer, "channel", None), "queue_lag", None)
                    if lag_fn is None:
                        continue
                    try:
                        lag = float(lag_fn(qname))
                    except Exception:
                        continue
                    yield Sample("apm_partition_lag",
                                 dict(lbl, partition=str(p)), lag, "gauge",
                                 "Unconsumed backlog of one owned partition queue")

    def _health(self) -> dict:
        """The /healthz engine section: tick liveness, emission/intake
        backlog, executor identity, device presence."""
        tracer = self.driver._tracer
        ring_alive = self._ring_thread is None or self._ring_thread.is_alive()
        out = {
            # a dead device loop wedges intake forever — the one internal
            # state that makes this process unhealthy on its own
            "ok": ring_alive,
            "executor": self.driver._step.kind,
            "services": self.driver.registry.count,
            "capacity": self.driver.cfg.capacity,
            "intake_backlog_lines": max(0, self._ring_pushed - self._ring_fed),
            "intake_dropped": self.intake_dropped,
            "emission_held": self.driver._pending_emission is not None,
            "overflow_row_ticks": self.driver.overflow_rows_total,
            "device_loop_alive": ring_alive,
        }
        with self._driver_lock:  # consistent healthz checkpoint block
            ck = {
                "mode": self._ckpt_mode,
                "write_failures": self._ckpt_failures_total,
                "fail_streak": self._ckpt_fail_streak,
                "degraded": self._ckpt_degraded,
            }
            if self._ckpt_degraded:
                # persistent checkpoint failure = cannot commit epochs = an
                # unhealthy worker the manager watchdog should see as 503
                out["ok"] = False
        if self._ckpt_chain is not None:
            ck["chain_epoch"] = self._ckpt_chain.tail_epoch
            ck["chain_dir"] = self._ckpt_chain.directory
        out["checkpoint"] = ck
        if self._at_least_once:
            with self._driver_lock:  # consistent healthz delivery block
                delivery = {
                    "mode": "atLeastOnce",
                    "epoch": self._delivery_epoch,
                    "unacked": len(self._epoch_tokens),
                    "pending_feed": len(self._alo_pending),
                    "deduped_total": self._deduped_total,
                    "dedup_window": self._window_total_locked(),
                }
                if self._fleet:
                    delivery["shard"] = self.shard_id
                    delivery["owned_partitions"] = sorted(
                        p for p in (
                            self._queue_partition(q) for q in self._windows
                        ) if p is not None
                    )
                    delivery["windows"] = {
                        q: len(w.fifo) for q, w in self._windows.items()
                    }
                    delivery["partition_mismatches"] = self._partition_mismatch_total
                # epoch-stall watchdog: intake exists but no epoch has
                # committed for epochStallSeconds — the shard is wedged (or
                # its disk is), and the manager /fleet plane must see 503
                stalled = (
                    self._epoch_stall_s > 0
                    and (self._epoch_tokens or self._alo_pending)
                    and time.monotonic() - self._last_epoch_commit
                    > self._epoch_stall_s
                )
                if stalled:
                    delivery["epoch_stalled"] = True
                    out["ok"] = False
                out["delivery"] = delivery
        # per-queue lag (backlog the consumer still owes) for every intake
        # queue whose transport can count it — the same numbers the
        # apm_queue_lag gauge scrapes and the lag SLO burns against
        lag = {}
        for qname, cq in self.in_queues.items():
            ch_lag = getattr(cq.channel, "queue_lag", None)
            if ch_lag is not None:
                try:
                    lag[qname] = int(ch_lag(qname))
                except Exception:
                    pass
        if lag:
            out["queue_lag"] = lag
        if tracer is not None:
            out.update(tracer.summary())
        try:
            import jax

            out["devices"] = [str(d) for d in jax.local_devices()]
        except Exception as e:
            out["devices_error"] = repr(e)
            out["ok"] = False
        return out

    # -- callbacks -----------------------------------------------------------
    def _on_fullstat_lines(self, lines) -> None:
        db_write = self.db_queue.write_line
        z_write = self.zscore_queue.write_line if self.zscore_queue is not None else None
        for line in lines:
            db_write(line)  # passthrough: everything lands in Postgres
            if z_write is not None:
                z_write(line)

    def _log_intake_stats(self) -> None:
        if self._ring is None:
            return
        self.runtime.logger.info(
            f"INTAKE> pushed: {self._ring_pushed} - fed: {self._ring_fed} - "
            # apm: allow(lock-guard): diagnostic log line; deque len is GIL-atomic and a stale count is fine
            f"ring bytes: {self._ring.used_bytes} - overflow: {len(self._overflow)} - "
            f"dropped: {self.intake_dropped} - reservoir row-ticks: "
            f"{self.driver.overflow_rows_total}"
        )

    @staticmethod
    def _real_device_memory_stats() -> dict:
        try:
            import jax

            return jax.local_devices()[0].memory_stats() or {}
        except Exception:
            return {}

    def _check_device_memory(self) -> None:
        stats = self._device_memory_stats()
        used = stats.get("bytes_in_use")
        if used is None:  # backend exposes no memory stats (e.g. CPU)
            return
        limit = stats.get("bytes_limit") or 0
        self.hbm_bytes_in_use = int(used)
        self.hbm_bytes_limit = int(limit)
        self.runtime.logger.info(
            f"HBM> in use: {used / 2**20:.1f} MiB"
            + (f" / {limit / 2**20:.1f} MiB ({used / limit:.0%})" if limit else "")
        )
        if limit and used / limit >= self._hbm_alarm_fraction:
            if not self._hbm_alerted:
                self._hbm_alerted = True
                self.ops_alerts.add(
                    f"Device memory at {used / limit:.0%} of {limit / 2**20:.0f} MiB "
                    f"(alarm fraction {self._hbm_alarm_fraction:.0%}): the next "
                    f"capacity growth or lag increase may OOM the chip. Shard the "
                    f"fleet across more devices or reduce serviceCapacity/"
                    f"samplesPerBucket/lags (or set zscoreRingDtype=bfloat16)."
                )
        elif self._hbm_alerted and limit and used / limit < self._hbm_alarm_fraction * 0.8:
            self._hbm_alerted = False  # re-arm after recovery with hysteresis

    def _on_overflow(self, label: int, n_rows: int) -> None:
        """Percentile-reservoir overflow -> manager alert, heavily rate-limited
        (first occurrence, then every 360 overflow ticks ~= 1h of log time)."""
        ticks = self.driver.overflow_ticks
        if ticks == 1 or ticks - self._overflow_alerted_ticks >= 360:
            self._overflow_alerted_ticks = ticks
            self.ops_alerts.add(
                f"Percentile sample reservoir overflowed for {n_rows} services at "
                f"bucket {label} ({self.driver.overflow_rows_total} row-ticks total): "
                f"percentiles for hot services are reservoir estimates. Raise "
                f"tpuEngine.samplesPerBucket to restore exactness."
            )

    def _note_intake(self, n: int) -> None:
        """Hand the oldest of the next ``n`` queued ingest stamps to the
        driver — called right before feeding n lines so queue + ring wait
        honestly counts toward the ingest->emit latency."""
        fifo = self._intake_ts_fifo
        oldest = None
        for _ in range(min(n, len(fifo))):
            try:
                ts = fifo.popleft()
            except IndexError:
                break
            if oldest is None or ts < oldest:
                oldest = ts
        if oldest is not None:
            self.driver.note_intake_time(oldest)

    def _trace_context(self, trace_id: str, headers: dict, line: str):
        """(trace_id, consume_ts, server, service, label, redelivered) for a
        sampled tx line, or None when the line is not a parseable tx."""
        p = line.split("|", 7)
        if len(p) < 8 or p[0] != "tx":
            return None
        try:
            label = int(float(p[6])) // 10000
        except ValueError:
            return None
        return (
            trace_id, time.time(), p[1], p[2], label,
            bool(headers.get("redelivered")),
        )

    def _note_trace_now(self, ctx) -> None:
        """Register one sampled trace with the driver right before its line
        is fed (feed span: transport delivery -> device absorb)."""
        tid, consume_ts, server, service, label, redelivered = ctx
        self.driver.note_trace(
            tid, server, service, label, consume_ts,
            redelivered=redelivered,
        )

    def _drain_trace_fifo(self, upto_seq: int) -> None:
        """Hand every queued sampled-trace context whose line is covered by
        the feed about to run (consume seq <= upto_seq) to the driver."""
        fifo = self._trace_fifo
        while fifo and fifo[0][0] <= upto_seq:
            _seq, ctx = fifo.popleft()
            self._note_trace_now(ctx)

    def _consume(self, line, headers=None, token=None, qname=None) -> None:
        if self._at_least_once:
            self._consume_at_least_once(line, headers, token, qname)
            return
        if isinstance(line, (bytes, bytearray, memoryview)) and _frames.is_frames(line):
            self._consume_frames(bytes(line), headers)
            return
        # transport ingest stamp (ProducerQueue header): queue it for the
        # feed-time handoff that anchors the ingest->emit/alert series.
        # trace_id marks the 1/rate sampled messages (obs/trace).
        trace_ctx = None
        if headers and self.driver._tracer is not None:
            ts = headers.get("ingest_ts")
            if ts is not None:
                self._intake_ts_fifo.append(ts)
            tid = headers.get("trace_id")
            if tid is not None and self.driver._trace is not None:
                trace_ctx = self._trace_context(tid, headers, line)
        if self._ring is not None and self._ring_thread.is_alive():
            # FIFO: while older overflow lines are pending, new lines must
            # queue behind them, not jump into the ring
            if self._overflow:  # apm: allow(lock-guard): single-producer emptiness probe; enqueue itself locks, and the consumer drains overflow before ring so FIFO holds either way
                self._enqueue_overflow(line)
                if trace_ctx is not None:
                    self._trace_fifo.append((self._ring_pushed, trace_ctx))
                return
            data = line.encode("utf-8")
            deadline = time.monotonic() + self._ring_spin_s
            while not self._ring.push(data):
                # ring full: brief blocking = backpressure; bounded so an
                # AMQP delivery callback keeps servicing heartbeats
                if self._ring_stop.is_set() or not self._ring_thread.is_alive():
                    break  # loop died: fall through to the direct path
                if time.monotonic() > deadline:
                    self._enqueue_overflow(line)
                    if trace_ctx is not None:
                        self._trace_fifo.append((self._ring_pushed, trace_ctx))
                    return
                time.sleep(0.001)
                # ring-full backpressure = the push stage blocked on the
                # device loop (sleep granularity is honest enough here)
                self._att_push.add_blocked(0.001)
            else:
                self._ring_pushed += 1
                if trace_ctx is not None:
                    self._trace_fifo.append((self._ring_pushed, trace_ctx))
                return
        # ring-less (or dead-loop) fallback: the per-line object path — one
        # from_csv + feed() is far cheaper than feed_csv_batch's numpy
        # machinery on a single line
        entry = self._factory.from_csv(line)
        if entry is None or entry.type != "tx":
            self.runtime.logger.info(f"Not a transactions entry: {line[:200]}")
            return
        self._note_intake(1)
        if trace_ctx is not None:
            self._note_trace_now(trace_ctx)
        with self._driver_lock:
            self.driver.feed(entry)

    def _frame_trace_context(self, trace_id: str, headers: dict, blob: bytes):
        """Trace context for a sampled frame batch: the batch's single
        trace_id anchors on its first parseable tx record (only 1/rate
        batches ever pay this decode)."""
        for lb in _frames.iter_lines(blob):
            ctx = self._trace_context(
                trace_id, headers, lb.decode("utf-8", "replace")
            )
            if ctx is not None:
                return ctx
        return None

    def _consume_frames(self, blob: bytes, headers) -> None:
        """One at-most-once packed-frame delivery: queue the blob for the
        device loop (bounded side FIFO — frames cannot ride the byte ring)
        or bulk-feed it directly when no ring is running."""
        n = _frames.frame_count(blob)
        if n == 0:
            return
        trace_ctx = None
        if self.driver._tracer is not None:
            h = headers or {}
            car = _frames.read_carriage(blob)
            if car is not None:
                # in-band APC1 carriage: true per-record parse-time stamps.
                # This is the only latency channel that survives the
                # header-less shm-ring direct-send path, and it keeps the
                # ingest->emit series honest per record instead of
                # flattening a whole batch onto one transport stamp.
                base, deltas, _tid = car
                self._intake_ts_fifo.extend(base + d / 1000.0 for d in deltas)
            else:
                ts = h.get("ingest_ts")
                if ts is not None:
                    # one stamp per record keeps _note_intake's n-for-n pop
                    # accounting aligned with the record counts feeds report
                    self._intake_ts_fifo.extend([ts] * n)
            # header trace_id wins (transport may have re-stamped); the
            # carriage tid backstops fabrics that carry no headers at all
            tid = h.get("trace_id") or _frames.carriage_trace_id(blob) or None
            if tid is not None and self.driver._trace is not None:
                trace_ctx = self._frame_trace_context(tid, h, blob)
        if (
            self._feed_frames
            and self._ring is not None
            and self._ring_thread.is_alive()
        ):
            self._enqueue_frames(blob, n)
            if trace_ctx is not None:
                self._trace_fifo.append((self._ring_pushed, trace_ctx))
            return
        # ring-less (or feedFrames=false compat) path: the batch is already
        # amortized, so feed it right here under the driver lock
        self._note_intake(n)
        if trace_ctx is not None:
            self._note_trace_now(trace_ctx)
        try:
            with self._driver_lock:
                if self._feed_frames:
                    self.driver.feed_frames(blob)
                else:
                    self.driver.feed_csv_batch(_frames.decode_lines(blob))
        except Exception:
            import traceback

            self.runtime.logger.error(
                f"Frame batch feed failed; {n} records dropped:\n"
                + traceback.format_exc()
            )

    def _enqueue_frames(self, blob: bytes, n: int) -> None:
        with self._frame_lock:
            self._frame_pending.append((blob, n))
            self._frame_pending_records += n
            self._att_frame_occ.sample(self._frame_pending_records)
            while self._frame_pending_records > self._overflow_max:
                _old, on = self._frame_pending.popleft()
                self._frame_pending_records -= on
                self.intake_dropped += on
                if self.intake_dropped % 10_000 == 1:
                    self.runtime.logger.error(
                        f"Frame intake overflow past {self._overflow_max} records "
                        f"while the device loop is stalled: {self.intake_dropped} "
                        f"oldest records dropped"
                    )
        self._ring_pushed += n

    def _drain_frames_locked_pop(self) -> list:
        with self._frame_lock:
            out = list(self._frame_pending)
            self._frame_pending.clear()
            self._frame_pending_records = 0
            self._att_frame_occ.sample(0)
        return out

    def _feed_frame(self, blob: bytes, n: int) -> None:
        self._feed_guarded(lambda: self.driver.feed_frames(blob), n)

    def _consume_at_least_once(self, line, headers, token, qname=None) -> None:
        """One manual-ack delivery: dedup against its queue's window,
        absorb, remember the token.

        Everything happens under the driver lock so the epoch commit
        (save_state) sees a consistent pair: the dedup windows it snapshots
        list exactly the messages whose effects are in the state it saves —
        the invariant that makes a crash between checkpoint and ack safe
        (redelivery → skip) AND a crash before checkpoint safe (redelivery →
        reprocess against the pre-epoch state)."""
        msg_id = (headers or {}).get("msg_id")
        frame = isinstance(line, (bytes, bytearray, memoryview)) and _frames.is_frames(line)
        if frame:
            # a frame batch is ONE delivery: one msg_id, one dedup entry,
            # one token — it is absorbed (or rejected) whole, never unfolded
            # at consume time
            line = bytes(line)
        if qname is None:
            qname = self._partition_base
        with self._driver_lock:
            w = self._windows.get(qname)
            if w is None:
                w = self._windows[qname] = _DedupWindow()
            if self._fleet:
                # routing discipline (shardmodel partition_header_mismatch
                # mutant): a message whose stamped partition contradicts the
                # queue it arrived on would strand its effect on a non-owner
                # — reject it LOUDLY (count + log), ack it at the epoch so
                # it cannot loop, and never absorb it.
                hp = (headers or {}).get("partition")
                expected = self._queue_partition(qname)
                mismatch = hp is not None and expected is not None \
                    and int(hp) != expected
                if not mismatch and frame and expected is not None:
                    # frame-level routing defense: the header can be right
                    # while records INSIDE the batch hash elsewhere (producer
                    # grouped by a drifted key). Reject the whole batch —
                    # partial absorption would strand the stray records'
                    # effects on a non-owner.
                    mismatch = _frames.count_partition_mismatches(
                        line, self._fleet_partitions, expected,
                        key=self._partition_key,
                    ) > 0
                if mismatch:
                    self._partition_mismatch_total += 1
                    if self._ev_fh is not None:
                        self._emit_event(
                            "deliver", msg=msg_id, queue=qname,
                            mismatch=True, dedup=False, tx=False,
                            redelivered=bool((headers or {}).get("redelivered")),
                        )
                    self.runtime.logger.error(
                        f"Partition mismatch on {qname}: stamped p{hp}"
                        f"{' (frame records hash elsewhere)' if frame else ''}, "
                        f"queue is p{expected} — delivery rejected "
                        f"(producer partitioner drift?)"
                    )
                    if token is not None:
                        self._epoch_tokens.append(token)
                    return
            is_tx = (
                _frames.tx_count(line) > 0 if frame else line.startswith("tx|")
            )
            if self._ev_fh is not None:
                self._emit_event(
                    "deliver", msg=msg_id, queue=qname,
                    dedup=msg_id is not None and msg_id in w.ids,
                    tx=is_tx,
                    redelivered=bool((headers or {}).get("redelivered")),
                )
            if msg_id is not None and msg_id in w.ids:
                # already absorbed: a broker redelivery or an in-flight
                # duplicate. Skip the feed, count it — but do NOT ack now:
                # an in-flight dup of a message absorbed in the CURRENT
                # (uncommitted) epoch shares the original's broker ledger
                # entry, and acking it here would advance the cursor past an
                # effect that is not yet durable (found by the kill−9
                # harness: one message lost per dup-then-crash). The token
                # joins the epoch and commits with everyone else.
                self._deduped_total += 1
                w.deduped += 1
                if token is not None:
                    self._epoch_tokens.append(token)
            else:
                if msg_id is not None:
                    w.ids.add(msg_id)
                    w.fifo.append(msg_id)
                    if self._ckpt_chain is not None:
                        # incremental window record for the delta commit:
                        # replay = (old + added)[evicted:]
                        w.added.append(msg_id)
                    if len(w.fifo) > self._dedup_max:
                        w.ids.discard(w.fifo.popleft())
                        if self._ckpt_chain is not None:
                            w.evicted += 1
                if is_tx:
                    h = headers or {}
                    ts = h.get("ingest_ts")
                    # sampled trace context rides the pending entry so the
                    # bulk drain registers it right before the feed; a broker
                    # redelivery kept the ORIGINAL trace_id (headers survive
                    # requeue like msg_id, and for frame batches the APC1
                    # carriage carries it IN the payload), so the trace
                    # extends across a crash instead of splitting
                    tid = h.get("trace_id")
                    if frame:
                        car = _frames.read_carriage(line)
                        if car is not None:
                            if ts is None:
                                ts = car[0]  # parse-time base stamp
                            tid = tid or (car[2] or None)
                    ctx = None
                    if tid is not None and self.driver._trace is not None:
                        ctx = (
                            self._frame_trace_context(tid, h, line)
                            if frame
                            else self._trace_context(tid, h, line)
                        )
                    self._alo_pending.append((line, ts, ctx, msg_id, qname))
                    if len(self._alo_pending) >= self._alo_batch:
                        self._drain_alo_pending_locked()
                elif frame:
                    self.runtime.logger.info(
                        f"Frame batch with no tx records dropped "
                        f"({_frames.frame_count(line)} records)"
                    )
                else:
                    # non-tx entries are rejected at accept time (same policy
                    # as before; malformed tx| lines are counted and logged
                    # by the bulk feed instead)
                    self.runtime.logger.info(f"Not a transactions entry: {line[:200]}")
                # every accepted line is "absorbed" (fed at the next drain,
                # or logged + dropped by policy): its token joins the epoch
                # so it is acked at commit, never redelivered forever
                if token is not None:
                    self._epoch_tokens.append(token)

    # apm: holds(_driver_lock): every caller acquires it (accept path, drain timer, save_state)
    def _drain_alo_pending_locked(self) -> None:
        """Feed the buffered at-least-once deliveries as one bulk batch
        (caller holds the driver lock).

        Failure path (protocol model checking, DESIGN.md §9.4): the dedup
        window's invariant is "membership ⇒ the message's effect reached
        the engine". The batch's ids were added at ACCEPT time, so if the
        bulk feed raises, leaving them in the window would turn a dropped
        batch into messages that are silently deduped forever — even
        their crash redeliveries would be skipped. On failure the batch's
        ids are withdrawn from the window (and from the delta-commit
        incremental record): a crash before the epoch commit then
        redelivers and reprocesses them; without a crash they are dropped
        loudly, same policy as the at-most-once feed path. Frame-mode
        streams interleave packed blobs with plain lines; deliveries are
        fed in arrival order as maximal same-kind runs, and a mid-run
        exception only withdraws the ids of deliveries NOT yet fed (the
        fed prefix's effects are in the engine — withdrawing those ids
        would let a crash redelivery double-count them)."""
        pending = self._alo_pending
        if not pending:
            return
        self._alo_pending = []
        if self.driver._tracer is not None:
            oldest = min((ts for _l, ts, _c, _m, _q in pending if ts is not None),
                         default=None)
            if oldest is not None:
                self.driver.note_intake_time(oldest)
            for _l, _ts, ctx, _m, _q in pending:
                # register sampled traces BEFORE the feed: the tick that
                # closes their bucket may fire inside this very batch
                if ctx is not None:
                    self._note_trace_now(ctx)
        fed = 0  # deliveries whose effects reached the engine
        try:
            n = len(pending)
            while fed < n:
                payload = pending[fed][0]
                if isinstance(payload, bytes):
                    # one packed frame batch = one delivery, straight down
                    # the columnar path (or unfolded HERE when
                    # tpuEngine.feedFrames is off — its token stays whole)
                    if self._feed_frames:
                        self.driver.feed_frames(payload)
                    else:
                        self.driver.feed_csv_batch(_frames.decode_lines(payload))
                    fed += 1
                else:
                    j = fed
                    while j < n and not isinstance(pending[j][0], bytes):
                        j += 1
                    self.driver.feed_csv_batch(
                        [line for line, _ts, _c, _m, _q in pending[fed:j]]
                    )
                    fed = j
        except Exception:
            import traceback

            import collections as _collections

            dropped = pending[fed:]
            by_q: dict = {}
            for _l, _ts, _c, m, q in dropped:
                if m is not None:
                    by_q.setdefault(q, set()).add(m)
            for q, ids in by_q.items():
                w = self._windows.get(q)
                if w is None:
                    continue
                w.ids -= ids
                w.fifo = _collections.deque(
                    m for m in w.fifo if m not in ids)
                if self._ckpt_chain is not None:
                    w.added = [m for m in w.added if m not in ids]
            self.runtime.logger.error(
                f"ALO bulk feed failed; {len(dropped)} deliveries dropped and "
                f"their ids withdrawn from the dedup window (crash-"
                f"redelivery will reprocess them):\n" + traceback.format_exc()
            )
            flight = getattr(self.runtime, "flight", None)
            if flight is not None:
                try:
                    flight.dump("worker_feed_exception")
                except Exception:
                    pass
            if fed:
                self._emit_event("feed", n=fed)
            return
        self._emit_event("feed", n=len(pending))

    def drain_delivery_pending(self) -> None:
        """Public drain hook (feed-delay timer + tests)."""
        with self._driver_lock:
            self._drain_alo_pending_locked()

    def _enqueue_overflow(self, line: str) -> None:
        with self._overflow_lock:
            self._overflow.append(line)
            self._att_overflow_occ.sample(len(self._overflow))
            if len(self._overflow) > self._overflow_max:
                self._overflow.popleft()
                self.intake_dropped += 1
                if self.intake_dropped % 10_000 == 1:
                    self.runtime.logger.error(
                        f"Intake overflow past {self._overflow_max} lines while the "
                        f"device loop is stalled: {self.intake_dropped} oldest lines dropped"
                    )
        self._ring_pushed += 1

    def _drain_overflow_locked_pop(self, max_batch: int) -> list:
        with self._overflow_lock:
            n = min(len(self._overflow), max_batch)
            out = [self._overflow.popleft() for _ in range(n)]
            self._att_overflow_occ.sample(len(self._overflow))
            return out

    def _ring_loop(self) -> None:
        """Device-loop thread: pop micro-batches off the intake ring and feed
        the bulk CSV path. Single popper + single pusher = the ring's SPSC
        contract. Overflowed lines (ring-full escape hatch) are older than
        anything pushed after them, so they drain once the ring is empty and
        block newer pushes until gone (FIFO preserved)."""
        recs: list = []  # raw byte records straight off the ring
        max_batch = 4096
        while not self._ring_stop.is_set():
            if self._frame_pending:  # apm: allow(lock-guard): consumer-side emptiness probe; the pop helper holds the lock
                # packed frame blobs (side FIFO — they cannot ride the ring)
                # drain ahead of newer ring entries, one bulk feed per blob
                if recs:
                    self._feed_recs(recs)
                    recs = []
                for blob, n in self._drain_frames_locked_pop():
                    self._feed_frame(blob, n)
                continue
            rec = self._ring.pop()
            if rec is None:
                if recs:
                    self._feed_recs(recs)
                    recs = []
                elif self._overflow:  # apm: allow(lock-guard): consumer-side emptiness probe; the pop helper holds the lock
                    batch = self._drain_overflow_locked_pop(max_batch)
                    if batch:
                        self._feed_lines(batch)
                else:
                    time.sleep(0.002)
                    # nothing to pop anywhere: the device loop is idle
                    self._att_feed.add_idle(0.002)
                continue
            recs.append(rec)
            if len(recs) >= max_batch:
                self._feed_recs(recs)
                recs = []
        while (rec := self._ring.pop()) is not None:  # final drain on stop
            recs.append(rec)
        if recs:
            self._feed_recs(recs)
        for blob, n in self._drain_frames_locked_pop():
            self._feed_frame(blob, n)
        tail = self._drain_overflow_locked_pop(self._overflow_max)
        if tail:
            self._feed_lines(tail)

    def _feed_recs(self, recs: list) -> None:
        """Byte records -> one blob -> the native bulk decode path (falls back
        to the numpy path inside feed_csv_bytes when no toolchain)."""
        self._feed_guarded(lambda: self.driver.feed_csv_bytes(b"\n".join(recs)), len(recs))

    def _feed_lines(self, lines: list) -> None:
        self._feed_guarded(lambda: self.driver.feed_csv_batch(lines), len(lines))

    def _feed_guarded(self, fn, n: int) -> None:
        self._note_intake(n)
        if self._trace_fifo:
            # sampled traces whose lines this feed absorbs go live on the
            # driver first: their tick may fire inside this very feed
            self._drain_trace_fifo(self._ring_fed + n)
        try:
            with self._att_feed.span(), self._driver_lock:
                fn()
        except Exception:
            # the device loop must survive a bad batch: a dead loop would
            # wedge the broker thread against a full ring forever. The batch
            # is lost; log loudly and keep consuming (crash-damping, like the
            # supervisor's module restarts).
            import traceback

            self.runtime.logger.error(
                f"Device loop: bulk feed failed; {n} lines dropped:\n"
                + traceback.format_exc()
            )
            flight = getattr(self.runtime, "flight", None)
            if flight is not None:
                # an unhandled feed exception is a flight-recorder trigger:
                # the bundle captures the tick rings/backlogs while the
                # wreckage is fresh (rate-limited — a poison batch loop must
                # not churn the bundle directory)
                try:
                    flight.dump("worker_feed_exception")
                except Exception:
                    pass
        finally:
            self._ring_fed += n

    @property
    def intake_pending(self) -> bool:
        """Lines accepted but not yet fed to the driver (ring in flight)."""
        return self._ring is not None and self._ring_fed < self._ring_pushed

    def drain_intake(self, timeout_s: float = 10.0) -> None:
        """Block until every line pushed so far has been fed to the driver
        (tests + orderly shutdown)."""
        if self._ring is None:
            return
        target = self._ring_pushed
        deadline = time.monotonic() + timeout_s
        while self._ring_fed < target and time.monotonic() < deadline:
            time.sleep(0.005)

    def _schedule_alert_send(self, interval_s: float) -> None:
        def _fire():
            try:
                count, next_interval = self.alerts_manager.flush()
                if count:
                    self.runtime.logger.info(f"Sent {count} alerts; next interval {next_interval}s")
            except Exception as e:
                self.runtime.logger.error(f"Alert send error: {e}")
                next_interval = interval_s
            self._schedule_alert_send(next_interval)

        if self.runtime._stop.is_set():
            return
        self._alert_timer = threading.Timer(interval_s, _fire)
        self._alert_timer.daemon = True
        self._alert_timer.start()

    def _apply_config(self, new_config: dict) -> None:
        with self._driver_lock:
            self.driver.apply_config(new_config)
        alerts_cfg = new_config.get("streamProcessAlerts", {})
        # emailsEnabled switched on at runtime needs the sender the startup
        # path skipped (and address changes should take effect)
        if alerts_cfg.get("emailsEnabled"):
            sender = EmailSender(
                alerts_cfg.get("fromEmail", "apm@localhost"),
                alerts_cfg.get("emailList", ""),
                logger=self.runtime.logger,
            )
            self.alerts_manager.email_sender = sender
            # hot-enabling emails must also arm the operational alerter
            if self.ops_alerts.email_sender is None:
                self.ops_alerts.email_sender = sender
            if not self._ops_alerts_started:
                self.ops_alerts.start()
                self._ops_alerts_started = True
        self.ops_alerts.set_config(new_config.get("applicationManager", {}))
        consume = bool(new_config.get("streamCalcStats", {}).get("consumeQueue", True))
        if consume != self._consume_enabled:
            self._consume_enabled = consume
            if consume:
                self._start_all_consume()
            else:
                self._stop_all_consume()
        self.alerts_manager.set_config(alerts_cfg)

    # -- state ---------------------------------------------------------------
    def _next_ckpt_backoff(self, prev: float) -> float:
        """Decorrelated-jitter retry delay for checkpoint write failures —
        the AMQP reconnect ``_next_backoff`` shape: ~U(base, 3·prev), capped
        (a fleet sharing one full filesystem must not retry in lockstep)."""
        return min(
            self._ckpt_backoff_max,
            self._ckpt_jitter.uniform(
                self._ckpt_backoff_base, max(prev * 3.0, self._ckpt_backoff_base)
            ),
        )

    # apm: holds(_driver_lock): called only from save_state's locked section
    def _ckpt_write_failed(self, err: Exception) -> None:
        """One failed checkpoint write: count, back off, and past the retry
        budget enter DEGRADED mode — flight bundle first (capture the
        wreckage while it is fresh), operator alert, intake paused so the
        broker absorbs the backlog (backpressure, not a crash loop)."""
        self._ckpt_failures_total += 1
        self._ckpt_fail_streak += 1
        self._ckpt_backoff = self._next_ckpt_backoff(self._ckpt_backoff)
        self._ckpt_retry_at = time.monotonic() + self._ckpt_backoff
        self.runtime.logger.error(
            f"Checkpoint write failed ({self._ckpt_fail_streak}/"
            f"{self._ckpt_max_retries} before degradation, retry in "
            f"{self._ckpt_backoff:.1f}s): {err}"
        )
        if self._ckpt_fail_streak != self._ckpt_max_retries or self._ckpt_degraded:
            return
        self._ckpt_degraded = True
        flight = getattr(self.runtime, "flight", None)
        if flight is not None:
            try:
                flight.dump("checkpoint_write_failure", force=True)
            except Exception:
                pass
        self.ops_alerts.add(
            f"Checkpoint writes failing persistently ({err}); epochs cannot "
            f"commit, so intake is PAUSED (unacked deliveries back up on the "
            f"broker) and retries continue with jittered backoff up to "
            f"{self._ckpt_backoff_max:.0f}s. Free disk space / fix the "
            f"checkpoint volume to resume."
        )
        if getattr(self, "in_queues", None) and self._consume_enabled:
            try:
                self._stop_all_consume()
                self._ckpt_paused_intake = True
            except Exception as e:
                self.runtime.logger.error(f"Degradation intake pause failed: {e}")

    # apm: holds(_driver_lock): called only from save_state's locked section
    def _ckpt_write_ok(self) -> None:
        if not self._ckpt_fail_streak and not self._ckpt_degraded:
            return
        self.runtime.logger.warning(
            f"Checkpoint writes recovered after {self._ckpt_fail_streak} failures"
        )
        self._ckpt_fail_streak = 0
        self._ckpt_backoff = 0.0
        self._ckpt_retry_at = None
        if self._ckpt_degraded:
            self._ckpt_degraded = False
            self.ops_alerts.add("Checkpoint writes recovered; intake resumed.")
            if self._ckpt_paused_intake and self._consume_enabled:
                try:
                    self._start_all_consume()
                except Exception as e:
                    self.runtime.logger.error(f"Degradation intake resume failed: {e}")
            self._ckpt_paused_intake = False

    # apm: holds(_driver_lock): every caller acquires it (commit paths, handoff)
    def _delivery_records_locked(self, next_epoch: int, incremental: bool) -> dict:
        """The per-queue delivery tree one commit persists: every owned
        queue's dedup window (full list, or the added/evicted incremental
        record for delta commits) stamped with the committing epoch. The
        set of records IS partition ownership in fleet mode."""
        out = {}
        for qname, w in self._windows.items():
            rec = {"epoch": next_epoch, "deduped_total": w.deduped}
            if incremental:
                rec["added"] = list(w.added)
                rec["evicted"] = w.evicted
            else:
                rec["dedup"] = list(w.fifo)
            out[qname] = rec
        return out

    # apm: holds(_driver_lock): every caller acquires it (commit paths)
    def _reset_window_increments_locked(self) -> None:
        for w in self._windows.values():
            w.added = []
            w.evicted = 0

    # apm: holds(_driver_lock): called only from save_state's locked section
    def _commit_checkpoint_locked(self, epoch_commit: bool) -> bool:
        """Write one checkpoint (delta append or full npz) with the delivery
        tree when an epoch is committing. Returns True when the write landed
        durably; False routes through the failure policy and MUST NOT ack."""
        from ..deltachain import CheckpointWriteError

        next_epoch = self._delivery_epoch + 1 if epoch_commit else self._delivery_epoch
        try:
            if self._ckpt_chain is not None:
                if not self._ckpt_chain.initialized:
                    # boot-time initialize failed (e.g. disk already full):
                    # keep trying to lay the base down under the same policy
                    self._ckpt_chain.initialize(
                        self.driver._capture_resume_arrays(None), epoch=0
                    )
                dd = None
                if epoch_commit:
                    dd = self._delivery_records_locked(next_epoch, True)
                chain_epoch = self.driver.save_resume_delta(
                    self._ckpt_chain, delivery_delta=dd
                )
                self._reset_window_increments_locked()
                self._maybe_compact_locked(chain_epoch, epoch_commit, next_epoch)
            else:
                delivery = None
                if epoch_commit:
                    delivery = self._delivery_records_locked(next_epoch, False)
                self.driver.save_resume(self.engine_resume, delivery=delivery)
        except (CheckpointWriteError, OSError) as e:
            self._ckpt_write_failed(e)
            self._emit_event("checkpoint", ok=False, mode=self._ckpt_mode,
                             epoch=self._delivery_epoch)
            return False
        if epoch_commit:
            self._delivery_epoch = next_epoch
            self._last_epoch_commit = time.monotonic()
        self._ckpt_write_ok()
        self._emit_event(
            "checkpoint", ok=True, mode=self._ckpt_mode,
            epoch=self._delivery_epoch if epoch_commit else None,
            chain_epoch=(self._ckpt_chain.tail_epoch
                         if self._ckpt_chain is not None else None),
        )
        return True

    # apm: holds(_driver_lock): called only from _commit_checkpoint_locked
    def _maybe_compact_locked(self, chain_epoch: int, epoch_commit: bool, next_epoch: int) -> None:
        """Kick the periodic full-snapshot compaction OFF the hot path: the
        locked section only captures the state arrays (device gathers); the
        compress + write + manifest swap + GC run on the chain's background
        thread while epochs keep appending."""
        if (
            self._ckpt_compact_every <= 0
            or chain_epoch - self._ckpt_last_compact < self._ckpt_compact_every
        ):
            return
        delivery = None
        if self._at_least_once and epoch_commit:
            delivery = self._delivery_records_locked(next_epoch, False)
        arrays = self.driver._capture_resume_arrays(delivery)
        # DEEP-COPY before handing off: np.asarray over CPU device buffers
        # can be zero-copy, and the tick loop's donated dispatches recycle
        # those buffers while the background thread is still serializing
        # (the exact use-after-donate shape behind the seed's old segfault,
        # tests/conftest.py) — save_resume is safe only because it
        # serializes synchronously under the driver lock
        arrays = {
            k: np.array(v, copy=True) if isinstance(v, np.ndarray) else v
            for k, v in arrays.items()
        }
        if self._ckpt_chain.compact_async(chain_epoch, arrays):
            self._ckpt_last_compact = chain_epoch
            self._emit_event("compact", chain_epoch=chain_epoch)

    def save_state(self, force: bool = False) -> None:
        """Snapshot device + alert state; in at-least-once mode this IS the
        epoch commit: flush → checkpoint (with the dedup window) → ack. The
        tokens are cleared only after the snapshot lands, so a failed save
        leaves them unacked (the broker redelivers; dedup absorbs).
        ``force`` (shutdown) bypasses the failure-backoff gate for one last
        attempt."""
        # the resume-save interval fires once at registration, which is
        # before the intake wiring exists: plain snapshot, no epoch to commit
        in_queue = getattr(self, "in_queue", None)
        tokens: list = []
        committed = True
        epoch_now = 0
        with self._driver_lock:
            if self._at_least_once:
                # batched intake MUST reach the engine before the snapshot:
                # the tokens below only commit effects the checkpoint holds
                self._drain_alo_pending_locked()
            self.driver.flush()
            if (
                not force
                and self._ckpt_retry_at is not None
                and time.monotonic() < self._ckpt_retry_at
            ):
                return  # backoff window after a failed checkpoint write
            has_ckpt = self._ckpt_chain is not None or self.engine_resume
            # idle skip (delta mode): an untouched engine with an empty
            # ledger has nothing to commit — appending empty delta segments
            # would grow every idle worker's chain once per save interval
            # and once per boot, for zero durability gain
            if (
                not force
                and self._ckpt_chain is not None
                and not self._epoch_tokens
                and not self._alo_pending
                and not self.driver.has_uncheckpointed_changes
                and not any(w.added or w.evicted for w in self._windows.values())
            ):
                return
            if self._at_least_once and in_queue is not None:
                tokens = self._epoch_tokens
                if has_ckpt:
                    committed = self._commit_checkpoint_locked(True)
                # no checkpoint configured: the "checkpoint" is process
                # memory — still ack per epoch (commit-to-memory batching)
                if committed:
                    self._epoch_tokens = []
                    if not has_ckpt:
                        self._last_epoch_commit = time.monotonic()
                else:
                    tokens = []  # unacked => redelivered; dedup absorbs
            elif has_ckpt:
                committed = self._commit_checkpoint_locked(False)
            epoch_now = self._delivery_epoch
        if tokens and committed:
            try:
                in_queue.ack(tokens)
                self._emit_event("ack", n=len(tokens), epoch=epoch_now)
            except Exception as e:
                # unacked => redelivered later; the saved dedup window makes
                # that a skip, not a double count
                self.runtime.logger.error(f"Epoch ack failed (will redeliver): {e}")
        if self.alerts_resume:
            self.alerts_manager.save_resume(self.alerts_resume)

    # -- quiesced rebalance handoff (shardmodel.py, DESIGN.md §10) -----------
    # The protocol implemented EXACTLY as pre-verified by the model checker:
    # ownership of partition p moves only when the releasing shard's unacked
    # ledger is empty (quiesce), and it moves TOGETHER with p's dedup-window
    # ids and p's state rows. The two commits are the linearization points —
    # the controller hands the handoff file to the adopter only after the
    # release commit lands, and the adopter owns p only once its import
    # commit lands; a crash on either side of either commit leaves the
    # partition in exactly one durable place (see the §10 failure matrix).

    # apm: holds(_driver_lock): called only from release/adopt locked sections
    def _handoff_commit_locked(self) -> bool:
        """Durably commit a handoff-mutated engine (rows removed or
        imported) + the new delivery tree. A wholesale row move is not
        representable as a dirty-cell delta, so delta mode writes a fresh
        full BASE at the current chain tail (sync compaction: the manifest
        swap IS the commit); full mode is a normal snapshot."""
        from ..deltachain import CheckpointWriteError

        next_epoch = self._delivery_epoch + 1
        delivery = self._delivery_records_locked(next_epoch, False)
        try:
            if self._ckpt_chain is not None:
                arrays = self.driver._capture_resume_arrays(delivery)
                arrays = {
                    k: np.array(v, copy=True) if isinstance(v, np.ndarray) else v
                    for k, v in arrays.items()
                }
                self._ckpt_chain.wait_compaction(timeout_s=60.0)
                self._ckpt_chain.compact(self._ckpt_chain.tail_epoch, arrays)
                self._ckpt_last_compact = self._ckpt_chain.tail_epoch
                self.driver._delta_reset_capture()
            elif self.engine_resume:
                self.driver.save_resume(self.engine_resume, delivery=delivery)
            # no checkpoint configured: process memory IS the state store
            # (test topologies); the in-memory windows moved already
        except (CheckpointWriteError, OSError) as e:
            self._ckpt_write_failed(e)
            self._emit_event("checkpoint", ok=False, mode=self._ckpt_mode,
                             epoch=self._delivery_epoch, handoff=True)
            return False
        self._delivery_epoch = next_epoch
        self._last_epoch_commit = time.monotonic()
        self._reset_window_increments_locked()
        self._ckpt_write_ok()
        self._emit_event(
            "checkpoint", ok=True, mode=self._ckpt_mode, epoch=next_epoch,
            chain_epoch=(self._ckpt_chain.tail_epoch
                         if self._ckpt_chain is not None else None),
            handoff=True,
        )
        return True

    def release_partition(self, p: int, out_path: str,
                          quiesce_timeout_s: float = 60.0) -> dict:
        """Release partition ``p``: quiesce (commit + ack until the unacked
        ledger is empty), write the handoff record (rows + window + chain
        manifest) to ``out_path``, then drop the rows/window/ownership and
        commit. Returns the handoff summary ONLY after the release commit
        landed — the file is inert (must be discarded) if this raises."""
        if not self._fleet:
            raise RuntimeError("release_partition requires fleet mode")
        from ..parallel.fleet import partition_queue, write_handoff

        qname = partition_queue(self._partition_base, p)
        if qname not in self.in_queues:
            raise ValueError(f"shard s{self.shard_id} does not own partition p{p}")
        # quiesce needs the WHOLE shard ledger empty (shardmodel: handoff
        # waits for `not s.ledgers[a]`), so all intake pauses briefly
        self._stop_all_consume()
        try:
            deadline = time.monotonic() + quiesce_timeout_s
            while True:
                self.save_state()
                with self._driver_lock:
                    quiesced = not self._epoch_tokens and not self._alo_pending
                if quiesced:
                    break
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"partition p{p} release: quiesce did not complete "
                        f"within {quiesce_timeout_s}s (checkpoint degraded?)"
                    )
                time.sleep(0.01)
            pred = self._partition_pred(p)
            with self._driver_lock:
                data = self.driver.export_service_rows(pred)
                w = self._windows.get(qname) or _DedupWindow()
                meta = {
                    "partition": p,
                    "queue": qname,
                    "base": self._partition_base,
                    "key": self._partition_key,
                    "shards": self._fleet_shards,
                    "partitions": self._fleet_partitions,
                    "from_shard": self.shard_id,
                    "epoch": self._delivery_epoch,
                    "window": list(w.fifo),
                    "deduped_total": w.deduped,
                    "rows": int(data["registry"].shape[0]),
                    "chain": (self._ckpt_chain.manifest_record()
                              if self._ckpt_chain is not None else None),
                }
                write_handoff(out_path, data, meta)
                self._emit_event(
                    "handoff_export", partition=p, queue=qname,
                    ids=list(w.fifo), rows=meta["rows"],
                    epoch=self._delivery_epoch,
                    unacked=len(self._epoch_tokens),
                )
                # the release: rows + window + ownership leave this shard,
                # then the commit makes it real
                self.driver.remove_service_rows(pred)
                self._windows.pop(qname, None)
                self.in_queues.pop(qname, None)
                if not self._handoff_commit_locked():
                    raise RuntimeError(
                        f"partition p{p} release commit failed (checkpoint "
                        f"error) — handoff file must be discarded"
                    )
                self._rebalances_total += 1
            if self.in_queues:
                self.in_queue = next(iter(self.in_queues.values()))
            self.runtime.logger.info(
                f"Released partition p{p} ({meta['rows']} rows, "
                f"{len(meta['window'])} window ids) -> {out_path}"
            )
            return meta
        finally:
            if self._consume_enabled:
                self._start_all_consume()

    def adopt_partition(self, p: int, in_path: str) -> dict:
        """Adopt partition ``p`` from a handoff record: import its state
        rows + dedup window, commit, and start consuming its queue. Safe to
        retry — a re-adopt of an already-owned partition (the controller
        retrying after an adopter crash that landed past the import commit)
        is a no-op."""
        if not self._fleet:
            raise RuntimeError("adopt_partition requires fleet mode")
        from ..parallel.fleet import partition_queue, read_handoff

        qname = partition_queue(self._partition_base, p)
        if qname in self.in_queues:
            if self._consume_enabled:
                self.in_queues[qname].start_consume()
            return {"partition": p, "rows": 0, "already_owned": True}
        data, meta = read_handoff(in_path)
        if meta.get("base") != self._partition_base \
                or int(meta.get("partition", -1)) != p:
            raise ValueError(
                f"handoff record mismatch: expected partition p{p} of "
                f"{self._partition_base!r}, file carries "
                f"p{meta.get('partition')} of {meta.get('base')!r}"
            )
        if int(meta.get("partitions", self._fleet_partitions)) \
                != self._fleet_partitions:
            # a record exported under a different keyspace grain routed its
            # rows by a different hash modulus — adopting it would violate
            # routing discipline for every row in it
            raise ValueError(
                f"handoff record mismatch: exporter ran "
                f"fleet.partitions={meta.get('partitions')}, this shard "
                f"runs {self._fleet_partitions}"
            )
        with self._driver_lock:
            # pending feeds of OUR queues must reach the engine before the
            # import commit snapshots it (drain-before-commit invariant)
            self._drain_alo_pending_locked()
            n_rows = self.driver.import_service_rows(data)
            w = _DedupWindow()
            for mid in meta.get("window", []):
                if mid not in w.ids:
                    w.ids.add(mid)
                    w.fifo.append(mid)
            w.deduped = int(meta.get("deduped_total", 0))
            self._windows[qname] = w
            self._emit_event(
                "handoff_import", partition=p, queue=qname,
                ids=list(w.fifo), rows=n_rows,
            )
            if not self._handoff_commit_locked():
                # roll the import back: the adopter must not serve rows it
                # cannot commit (the controller will retry the adopt)
                self._windows.pop(qname, None)
                pred = self._partition_pred(p)
                self.driver.remove_service_rows(pred)
                self._emit_event(
                    "handoff_abort", partition=p, queue=qname,
                    ids=list(w.fifo),
                )
                raise RuntimeError(
                    f"partition p{p} adopt commit failed (checkpoint error) "
                    f"— import rolled back, retry the adopt"
                )
            self._rebalances_total += 1
        consumer = self._open_partition_queue(p)
        if self._consume_enabled:
            consumer.start_consume()
        self.runtime.logger.info(
            f"Adopted partition p{p} ({n_rows} rows, "
            f"{len(meta.get('window', []))} window ids) from s"
            f"{meta.get('from_shard')}"
        )
        return {"partition": p, "rows": n_rows, "from_shard": meta.get("from_shard")}

    def owned_partitions(self) -> list:
        """Sorted partition ids this shard currently owns (fleet mode)."""
        return sorted(
            p for p in (self._queue_partition(q) for q in list(self.in_queues))
            if p is not None
        )

    # -- durable control-file channel ---------------------------------------
    @staticmethod
    def _read_ctl_seq(done_path: str) -> int:
        import json as _json

        try:
            with open(done_path, "r", encoding="utf-8") as fh:
                return int(_json.load(fh).get("seq", 0))
        except (OSError, ValueError):
            return 0

    def _exec_control(self, req: dict) -> dict:
        """Execute one control request -> the durable done record. Shared
        by the harness child's inline poll and the controlDir timer; never
        raises — the controller reads the error and decides (retry/abort),
        the worker stays up."""
        seq = int(req.get("seq", 0))
        try:
            cmd = req.get("cmd")
            if cmd == "release":
                result = self.release_partition(
                    int(req["partition"]), req["path"])
            elif cmd == "adopt":
                result = self.adopt_partition(
                    int(req["partition"]), req["path"])
            elif cmd == "owned":
                result = {"partitions": self.owned_partitions()}
            else:
                raise ValueError(f"unknown control command {cmd!r}")
            return {"seq": seq, "ok": True, "result": result}
        except Exception as e:
            return {"seq": seq, "ok": False,
                    "error": f"{type(e).__name__}: {e}"}

    def _poll_control_file(self) -> None:
        import json as _json

        try:
            with open(self._ctl_path, "r", encoding="utf-8") as fh:
                req = _json.load(fh)
        except (OSError, ValueError):
            return
        seq = int(req.get("seq", 0))
        if seq <= self._ctl_last:
            return
        out = self._exec_control(req)
        self._ctl_last = seq
        tmp = self._ctl_done_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            _json.dump(out, fh, default=repr)
        os.replace(tmp, self._ctl_done_path)

    def shutdown(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._alert_timer is not None:
            self._alert_timer.cancel()
        if self._ring_thread is not None:
            self.drain_intake()  # everything consumed must reach the device
            self._ring_stop.set()
            # a registry-growth recompile inside the loop can take tens of
            # seconds on real TPU: wait long, and NEVER destroy the native
            # ring under a live popper (use-after-free) — leaking it on a
            # stuck exit is harmless, the process is going down anyway
            self._ring_thread.join(timeout=60.0)
            if self._ring_thread.is_alive():
                self.runtime.logger.error(
                    "Device loop did not exit within 60s; leaving intake ring allocated"
                )
            else:
                self._ring.close()
        # final flush sends whatever is buffered (sendAlertsRecurse(0, true)
        # on exit, stream_process_alerts.js:575)
        try:
            self.alerts_manager.flush()
        except Exception as e:
            self.runtime.logger.error(f"Final alert flush error: {e}")
        self.ops_alerts.stop()
        try:
            self.ops_alerts.flush()
        except Exception as e:
            self.runtime.logger.error(f"Final ops-alert flush error: {e}")
        self.save_state(force=True)
        if self._ckpt_chain is not None:
            # a compaction still running is crash-safe to abandon (the old
            # manifest stays valid), but an orderly exit gives it a moment
            self._ckpt_chain.wait_compaction(timeout_s=30.0)
        if self._ev_fh is not None:
            fh, self._ev_fh = self._ev_fh, None
            with self._ev_lock:
                try:
                    fh.close()
                except Exception:
                    pass


def build(runtime) -> WorkerApp:
    return WorkerApp(runtime)


def main(config_path: Optional[str] = None, broker: Optional[MemoryBroker] = None) -> None:
    from .module_base import ModuleRuntime

    runtime = ModuleRuntime("tpuEngine", config_path=config_path, broker=broker)
    build(runtime)
    runtime.logger.info("TPU pipeline worker started")
    runtime.run_forever()


if __name__ == "__main__":
    main()
