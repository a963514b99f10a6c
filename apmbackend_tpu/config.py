"""Configuration system for the TPU-native APM backend.

Reproduces the reference's config semantics (see /root/reference/util_methods.js:253-348
and /root/reference/config/apm_config.json):

- A single JSON file shared by every module, allowing ``//`` line comments that are
  stripped before parsing unless preceded by ``:`` (so URLs like ``amqp://`` survive).
- Hard failure (exit code 2) when the file is missing on first load.
- Hot reload: the file is watched; a change is debounced, then md5+size compared,
  and only a *parseable* new config is applied — a broken edit keeps the old config
  live until corrected (util_methods.js:297-348).
- ``restart_required_vars``: dotted paths that only warn when changed at runtime.
- Hierarchical per-service overrides (e.g. z-score THRESHOLD/INFLUENCE per lag,
  apm_config.json:152-172) are resolved by :func:`resolve_path` helpers.

Unlike the reference the watcher here is polling-based (mtime+md5), which behaves
identically on NFS where inotify is unreliable — the same reason the reference
shipped a patched Perl File::Tail.
"""

from __future__ import annotations

import copy
import hashlib
import json
import os
import re
import sys
import threading
from typing import Any, Callable, Iterable, Optional

# Strip // comments unless preceded by ':' (keeps URLs intact). Mirrors
# JSONstrip (util_methods.js:265-268) which removes '[^:]//...' to end of line.
_COMMENT_RE = re.compile(r"(?<!:)//[^\n]*")


def strip_json_comments(text: str) -> str:
    """Remove ``//`` comments (not ``://``) from JSON text."""
    out_lines = []
    for line in text.splitlines():
        stripped = line.lstrip()
        if stripped.startswith("//"):
            out_lines.append("")
            continue
        out_lines.append(_COMMENT_RE.sub("", line))
    return "\n".join(out_lines)


def resolve_path(obj: Any, path: str, separator: str = ".") -> Any:
    """Resolve a dotted path into nested dicts, returning None when absent.

    Mirrors ``resolve`` (util_methods.js:248-251).
    """
    cur = obj
    for part in path.split(separator):
        if cur is None:
            return None
        if isinstance(cur, dict):
            cur = cur.get(part)
        else:
            cur = getattr(cur, part, None)
    return cur


class ConfigError(Exception):
    pass


def load_config(path: str, *, logger=None, exit_on_missing: bool = False) -> dict:
    """Read + parse the APM config file.

    Mirrors ``readAPMConfig`` (util_methods.js:253-295): missing file is fatal
    (exit 2) when ``exit_on_missing``; unparseable content returns None-equivalent
    (raises ConfigError) so a watcher can keep the previous config.
    """
    if not os.path.exists(path):
        msg = f"APM config file does not exist, can't continue: {path}"
        if logger:
            logger.warning(msg)
        if exit_on_missing:
            sys.exit(2)
        raise ConfigError(msg)
    with open(path, "r", encoding="utf-8") as fh:
        content = fh.read()
    try:
        config = json.loads(strip_json_comments(content))
    except json.JSONDecodeError as e:
        raise ConfigError(f"Could not parse JSON content from APM config file: {path}: {e}") from e
    config["apmConfigFilePath"] = path
    return config


class ConfigWatcher:
    """Poll a config file and invoke a callback when its content changes.

    Debounce + md5/size change detection per util_methods.js:301-316. A parse
    failure keeps the previous config and waits for a correction. Vars listed in
    ``restart_required_vars`` only produce a warning when changed.
    """

    def __init__(
        self,
        path: str,
        update_callback: Callable[[dict], None],
        restart_required_vars: Iterable[str] = (),
        *,
        poll_interval: float = 0.5,
        logger=None,
    ):
        self.path = path
        self.update_callback = update_callback
        self.restart_required_vars = list(restart_required_vars)
        self.poll_interval = poll_interval
        self.logger = logger
        self._prev_md5 = self._digest()
        self._current = load_config(path, logger=logger)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @property
    def current(self) -> dict:
        return self._current

    def _digest(self) -> str:
        try:
            with open(self.path, "rb") as fh:
                return hashlib.md5(fh.read()).hexdigest()
        except OSError:
            return ""

    def check_once(self) -> Optional[dict]:
        """Single poll step; returns the new config if one was applied."""
        digest = self._digest()
        if digest == self._prev_md5 or not digest:
            return None
        self._prev_md5 = digest
        try:
            new_config = load_config(self.path, logger=self.logger)
        except ConfigError:
            if self.logger:
                self.logger.warning(
                    "The config file JSON could not be processed, proceeding with NO "
                    "config changes. Future config corrections will be picked up."
                )
            return None
        prev = self._current
        for var in self.restart_required_vars:
            old_val = resolve_path(prev, var)
            new_val = resolve_path(new_config, var)
            if json.dumps(old_val, sort_keys=True) != json.dumps(new_val, sort_keys=True):
                if self.logger:
                    self.logger.warning(
                        f"{var} was changed on settings reload, but this will not take "
                        f"effect without a restart. Old={old_val!r} New={new_val!r}"
                    )
        self._current = new_config
        self.update_callback(new_config)
        return new_config

    def start(self) -> None:
        if self._thread is not None:
            return

        def _loop():
            while not self._stop.wait(self.poll_interval):
                try:
                    self.check_once()
                except Exception as e:  # watcher must never die
                    if self.logger:
                        self.logger.error(f"Config watcher error: {e}")

        self._thread = threading.Thread(target=_loop, name="apm-config-watcher", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None


def service_zscore_settings(zscore_config: dict, service: str) -> list[dict]:
    """Resolve per-service z-score lag settings with overrides applied.

    Mirrors ``getServiceSettingsFromConfig`` (stream_calc_z_score.js:106-132):
    defaults is a list of {LAG, THRESHOLD, INFLUENCE}; overrides.services.<name>
    maps lag-string -> partial {THRESHOLD, INFLUENCE}.
    """
    settings = [dict(s) for s in zscore_config.get("defaults", [])]
    overrides = (zscore_config.get("overrides", {}) or {}).get("services", {}) or {}
    service_overrides = overrides.get(service)
    if service_overrides:
        for setting in settings:
            for lag_key, vals in service_overrides.items():
                if int(setting["LAG"]) == int(lag_key):
                    if vals.get("THRESHOLD"):
                        setting["THRESHOLD"] = vals["THRESHOLD"]
                    if vals.get("INFLUENCE"):
                        setting["INFLUENCE"] = vals["INFLUENCE"]
    return settings


def service_alert_overrides(alerts_config: dict, service: str) -> Optional[dict]:
    """Per-service alert threshold overrides (stream_process_alerts.js:335-346)."""
    overrides = (alerts_config.get("overrides", {}) or {}).get("services", {}) or {}
    return overrides.get(service)


def service_ewma_overrides(eng_config: dict, service: str) -> dict:
    """Per-service EWMA-channel overrides: channel-id-string -> partial
    {THRESHOLD, INFLUENCE}, null-safe and truthiness-filtered exactly like
    :func:`service_zscore_settings` (falsy values are ignored, matching
    stream_calc_z_score.js:106-132 — a 0 override is a no-op, not a
    signal-on-everything threshold)."""
    overrides = (eng_config.get("ewmaChannelOverrides", {}) or {}).get("services", {}) or {}
    chans = overrides.get(service) or {}
    out = {}
    for chan_key, vals in chans.items():
        kept = {
            k: vals[k] for k in ("THRESHOLD", "INFLUENCE") if vals.get(k)
        }
        if kept:
            out[int(chan_key)] = kept
    return out


def default_config() -> dict:
    """A complete default config mirroring the reference's shipped apm_config.json

    (structure and defaults from /root/reference/config/apm_config.json), with
    paths relative to the repo and TPU-engine settings added under ``tpuEngine``.
    """
    return copy.deepcopy(_DEFAULT_CONFIG)


_DEFAULT_CONFIG: dict = {
    "appDirectory": ".",
    "amqpConnectionString": "amqp://localhost:5672",
    "brokerBackend": "memory",  # "memory" | "amqp" | "redis" | "spool" | "shmring"
    # consumer prefetch for at-least-once (manual-ack) AMQP consumers: the
    # broker bound on in-flight unacked deliveries per connection — also the
    # worst-case redelivery span a dedup window must cover
    "amqpPrefetchCount": 1000,
    # End-to-end flow control (transport/base.py, DESIGN.md §7.1): the
    # producer pause buffer — what write_line holds while the broker refuses
    # — is capped; past the cap the oldest lines are evicted under
    # producerOverflowPolicy ("drop-oldest": counted loss, the at-least-once
    # layer's dedup absorbs any overlap; "spill-spool": evictions land in a
    # durable spool under spillDirectory for offline replay) and the episode
    # degrades loudly (flight bundle + decision record + counter).
    "transport": {
        # broker selection override; None defers to top-level brokerBackend
        # (kept for config compatibility with pre-ISSUE-15 deployments)
        "broker": None,
        "producerBufferMaxLines": 100000,  # 0 = legacy unbounded
        "producerOverflowPolicy": "drop-oldest",  # | "spill-spool"
        "spillDirectory": "spool/overflow",
        # brokerBackend "spool": directory of the shared durable spool fabric
        "spoolDirectory": "spool/broker",
        # /healthz flow-control provider degrades when any producer buffer
        # reaches this fraction of the cap (pages BEFORE eviction starts)
        "producerBufferDegradedRatio": 0.8,
        # Zero-object byte spine (transport/frames.py, DESIGN.md §4.1):
        # frameMode makes the parser emit packed APF1 frame batches — one
        # write_frames per batch, headers stamped once per batch — instead
        # of one write_line per record. OFF keeps the wire bit-identical to
        # the pre-frame backend; APM_NO_FRAMES=1 is the runtime kill
        # switch, APM_FRAMES_NO_NATIVE=1 forces the Python encoder.
        "frameMode": False,
        "frameMaxRecords": 512,  # records per batch before a forced flush
        # brokerBackend "shmring": mmap'd SPSC shared-memory rings (one
        # file per queue under shmRingDirectory, shmRingBytes each) for the
        # parser->worker hop — at-most-once, in-host, zero broker process.
        "shmRingDirectory": "spool/shmring",
        "shmRingBytes": 8 * 1024 * 1024,
    },
    # Redis Streams backend (transport/redis_streams.py): consumer groups
    # give manual-ack/redelivery via the PEL + XAUTOCLAIM; send refuses while
    # the group backlog is at streamMaxlen (retention trims at 2x, so only
    # the acked prefix is ever dropped). claimIdleMs is how long a delivery
    # may sit unacked before another consumer may steal it — the redis
    # analog of the AMQP redelivery-on-connection-death span.
    "redis": {
        "connectionString": "redis://localhost:6379/0",
        "streamMaxlen": 100000,
        "group": "apm",
        "claimIdleMs": 5000,
        "prefetchCount": 1000,
    },
    "logDir": "logs",
    "statLogIntervalInSeconds": 60,
    "dbInsertQueue": "db_insert",
    # Telemetry plane (apmbackend_tpu.obs): per-stage tick tracing, queue/
    # parser/DB counters, and — when a module section sets "metricsPort"
    # (0 = ephemeral) — a per-module HTTP exporter serving Prometheus
    # /metrics, JSON /healthz, and on-demand /profile. "enabled": false
    # removes every instrument from the hot paths.
    "observability": {
        "enabled": True,
        "metricsHost": "127.0.0.1",
        # Distributed trace plane (obs/trace): head-sample every Nth message
        # at transport entry (trace_id header + per-hop spans served by the
        # exporter's /trace; histograms gain bucket exemplars). 0 disables
        # sampling entirely — the wire and the hot path are then bit-identical
        # to the pre-trace backend.
        "traceSampleRate": 64,
        "traceRingSize": 512,
        # Crash flight recorder (obs/flight): triage-bundle directory (None =
        # disabled). Bundles are dumped on healthz degradation, SIGTERM/SIGINT,
        # worker feed exceptions, and on demand via /flight; a journal +
        # alive-sentinel shadow rewritten every flightJournalSeconds survives
        # kill−9 and is promoted to a crash bundle on the next boot.
        "flightDir": None,
        "flightJournalSeconds": 5.0,
        "flightMaxBundles": 16,
        # Durable telemetry spine (obs/store + obs/recorder, DESIGN.md §8.4).
        # recorderDir enables the MANAGER-side fleet recorder: every child's
        # /metrics, /trace, and /decisions scraped each recorderIntervalSeconds
        # and persisted shard-labeled into an append-only segmented store, so
        # a kill−9'd shard's last telemetry stays queryable via /query.
        "recorderDir": None,
        "recorderIntervalSeconds": 2.0,
        "recorderRetentionSeconds": 3600.0,
        "recorderDownsampleAfterSeconds": 900.0,
        "recorderDownsampleStepSeconds": 60.0,
        # Per-module store behind each exporter's /query: registry snapshots
        # every selfSampleSeconds (0 disables /query + the local store);
        # storeDir=None keeps it in-memory (volatile, still queryable).
        "storeDir": None,
        "selfSampleSeconds": 2.0,
        "storeRetentionSeconds": 900.0,
    },
    # Fleet query plane (obs/queryplane, DESIGN.md §10.5): the manager
    # replaces its per-process /query /trace /decisions /attrib with a
    # fleet-wide router — single-service queries go to the owning shard
    # via the pinned service_partition hash + live owner map, everything
    # else scatter-gathers (counters summed, histogram buckets merged
    # before the quantile, spans/decisions deduped) and a dead shard is
    # served from the recorder store with explicit partial/stale marking.
    # Its /attrib and /trace fold in the manager's own plane and span ring;
    # disabled, the manager serves those of its own process only.
    "queryPlane": {
        "enabled": True,
        # TTL read-through cache for dashboard-repeated queries; 0 disables
        "cacheTtlSeconds": 2.0,
        # bounded shard fan-out concurrency per request
        "fanoutConcurrency": 8,
        # per-shard sub-request timeout; a slower shard degrades to the store
        "timeoutSeconds": 2.0,
        # bounded requeries when the owner map seq moves mid-fanout
        "moveRetries": 2,
        # owner-map refresh cadence (manager re-derives it from shard
        # scrapes; the standalone CLI polls /fleet at this cadence)
        "ownerRefreshSeconds": 5.0,
    },
    # SLO burn-rate engine (obs/slo, DESIGN.md §8.4): Google-SRE multi-window
    # burn rates evaluated over the telemetry store. A "fast" burn (both
    # windows >= fastBurnThreshold) pages through the alert/decision path and
    # degrades /healthz to 503; "slow" burns ticket at slowBurnThreshold.
    # objectives=None uses the built-in four (detection latency p95, alert
    # latency, per-queue wait/lag, epoch age — obs.slo.DEFAULT_OBJECTIVES);
    # override with a list of {name, kind: latency|gauge, series,
    # thresholdSeconds|threshold, target, per}.
    "slo": {
        "enabled": True,
        "evaluationIntervalSeconds": 10.0,
        "shortWindowSeconds": 300.0,
        "longWindowSeconds": 3600.0,
        "fastBurnThreshold": 14.4,
        "slowBurnThreshold": 6.0,
        "alertCooldownSeconds": 300.0,
        "objectives": None,
    },
    "statistics": [
        {"type": "average"},
        {"type": "percentile", "percentileValue": 75},
        {"type": "percentile", "percentileValue": 95},
    ],
    "applicationManager": {
        "logFilePrefix": "apm_manager",
        "fromEmail": "apm@example.com",
        "emailsEnabled": False,
        "emailList": "admin@example.com",
        "alertCollectionIntervalInSeconds": 60,
        "increaseCollectionIntervalAfterAlert": True,
        "maxCollectionIntervalInSeconds": 3840,
        "queueMessageAlertThreshold": 1000000,
        "queueMemoryAlertThreshold": 150,
        "moduleMemoryAlertThreshold": 350,
        "moduleSwapAlertThreshold": 200,
        "diskSpaceGBAvailableThreshold": 100,
        "diskSpacePercentageUsedThreshold": 80,
        "inspectionFrequencySeconds": 60,
        # hung-tick watchdog: a child whose /healthz answers 503 (or times
        # out) this many consecutive inspection cycles is force-restarted
        # through the crash-loop-damped path (0 disables; only children with
        # a metricsPort are watchable)
        "healthzFailureThreshold": 3,
        "healthzTimeoutSeconds": 2,
        "sendAlertOnUnexpectedScriptEnd": True,
        "triggerGCThreshold": 500,
        "appLogRetentionDays": 7,
        # disk inspection mount point (None = appDirectory) and the RabbitMQ
        # sbin dir for broker admin commands ("" = resolve from PATH)
        "diskInspectionMount": None,
        "rabbitSbinPath": "",
        # full child teardown on manager shutdown (default: children keep
        # running so a manager restart is non-disruptive)
        "stopChildrenOnShutdown": False,
        # per-child "metricsPort" makes the child a /fleet scrape target of
        # the manager's exporter (see tools.qstat --metrics-url, DESIGN.md)
        "moduleSettings": [
            {"module": "apmbackend_tpu.ingest.parser_main"},
            {"module": "apmbackend_tpu.runtime.worker", "moduleMemoryAlertThreshold": 700},
            {"module": "apmbackend_tpu.sinks.insert_db_main"},
            {"module": "apmbackend_tpu.ingest.jmx_main"},
        ],
        "metricsPort": None,  # the manager's own /metrics + /fleet exporter
    },
    "streamParseTransactions": {
        "logFilePrefix": "stream_parse_transactions",
        "outQueue": "transactions",
        "verboseQueueWrite": False,
        "tailPauseFileFullPath": "state/PAUSE_TAILS.switch",
        "appLogDirMaskPrefix": "fixtures/logs",
        "maskSuffixes": ["app*log", "server.log", "soap_io*log"],
        # server-name extraction from a log path: regex (group 1), else path
        # component (reference: split('/')[2]), else fixed default, else basename
        "serverFromPathPattern": None,
        "serverPathComponentIndex": 2,
        "defaultServerName": None,
        # optional path to the native C++ tail binary (native/apm_tail);
        # Python tailer threads are used when absent
        # per-file tail process: "auto" builds native/tailer.cpp via make and
        # spawns it per file (perl_tail.pl role); an explicit path uses that
        # binary; None uses in-process Python tail threads
        "nativeTailBinary": "auto",
        "metricsPort": None,  # telemetry exporter port (0 = ephemeral)
    },
    "streamCalcStats": {
        "logFilePrefix": "stream_calc_stats",
        "logDebug": False,
        "inQueue": "transactions",
        "outQueue": "stats",
        "consumeQueue": True,
        "verboseQueueWrite": False,
        "resumeFileFullPath": "save/stream_calc_stats.resume",
        "resumeFileSaveFrequencyInSeconds": 60,
        "intervalLengthInSeconds": 10,
        "windowSizeInIntervals": 30,
        "bufferSizeInIntervals": 6,
    },
    "streamCalcZScore": {
        "logFilePrefix": "stream_calc_z_score",
        "inQueue": "stats",
        "outQueue": "z_score",
        "consumeQueue": True,
        "verboseQueueWrite": False,
        "resumeFileFullPath": "save/stream_calc_z_score.resume",
        "resumeFileSaveFrequencyInSeconds": 60,
        # Per-lag baselining windows (apm_config.json:136-145 shape). Each
        # entry may also set "ROBUST": true to baseline with median/MAD
        # (1.4826 consistency scaling) instead of mean/std — immune to the
        # classic z-score's self-contamination, where an outlier burst
        # inflates the window std and masks later anomalies until it ages
        # out (no reference equivalent; per-lag static, recompiles on change).
        "defaults": [
            {"LAG": 360, "THRESHOLD": 20.0, "INFLUENCE": 0.1},
            {"LAG": 8640, "THRESHOLD": 15.0, "INFLUENCE": 0.0},
        ],
        "overrides": {"services": {}},
    },
    "streamProcessAlerts": {
        "logFilePrefix": "stream_process_alerts",
        "inQueue": "z_score",
        "consumeQueue": True,
        "verboseQueueWrite": False,
        "alertsResumeFileFullPath": "save/stream_process_alerts.resume",
        "resumeFileSaveFrequencyInSeconds": 60,
        "ignoreOldAlertsDuringCatchupLimitInMinutes": 60,
        "hardMinMsAlertThreshold": 200,
        "hardMaxMsAlertThreshold": 10000,
        "hardMinTpmAlertThreshold": 1.0,
        "alertOnBothOnly": True,
        "overrides": {"services": {}},
        "suppressedLags": [],
        "rollingAlertWindowSizeInIntervals": 60,
        "requiredNumberBadIntervalsInAlertWindowToTrigger": 45,
        "suppressedServices": [],
        "perServiceAlertCooldownInMinutes": 15,
        "alertCollectionIntervalInSeconds": 60,
        "increaseCollectionIntervalAfterAlert": True,
        "maxCollectionIntervalInSeconds": 960,
        "fromEmail": "apm@example.com",
        "emailsEnabled": False,
        "emailList": "oncall@example.com",
        "testEmailList": "admin@example.com",
    },
    "streamInsertDb": {
        "logFilePrefix": "stream_insert_db",
        "consumeQueue": True,
        "bufferResumeFileFullPath": "save/stream_insert_db_buffer.resume",
        "dbBackend": "fake",  # "fake" | "postgres" | "sqlite"
        "dbUser": "prod",
        "dbHost": "localhost",
        "dbDatabase": "apm",
        "dbTxTable": "tx",
        "dbStatTable": "stats",
        "dbAlertTable": "alerts",
        "dbJmxTable": "jmx",
        "dbInsertBufferLimit": 1000,
        "dbMaxTimeBetweenInsertsMs": 5000,
        # sqlite backend file (":memory:" = ephemeral); postgres credentials
        "dbFileFullPath": ":memory:",
        "dbPassword": None,
        "dbPort": 5432,
        "metricsPort": None,  # telemetry exporter port (0 = ephemeral)
    },
    "pullJvmStats": {
        "logFilePrefix": "pull_jvm_stats",
        "verboseQueueWrite": False,
        "clientJarFullPath": None,  # path to jboss-cli-client.jar; None => polling disabled
        "jvmHosts": [],
        "shortenHostname": True,
        "adminUser": "",
        "adminPass": "",
        "jmxPort": 9990,
        "clientTimeoutMs": 2000,
        "pollingIntervalSeconds": 60,
        "metricsPort": None,  # telemetry exporter port (0 = ephemeral)
        # resource label -> jboss-cli command; order defines blob labeling
        # (config/apm_config.json:246-254)
        "statCmdMap": {
            "ds": "/subsystem=datasources/data-source=DefaultDS/statistics=pool:read-resource(include-runtime=true,recursive=true)",
            "heap": "/core-service=platform-mbean/type=memory :read-attribute(name=heap-memory-usage)",
            "meta": "/core-service=platform-mbean/type=memory :read-attribute(name=non-heap-memory-usage)",
            "sysload": "/core-service=platform-mbean/type=operating-system :read-attribute(name=system-load-average)",
            "classcnt": "/core-service=platform-mbean/type=class-loading :read-attribute(name=loaded-class-count)",
            "threading": "/core-service=platform-mbean/type=threading :read-resource",
            "bean": "/deployment=App.ear/subdeployment=*/subsystem=ejb3/stateless-session-bean=MainBean :read-resource(recursive=true,include-runtime=true)",
        },
        # device multivariate anomaly detector over the poll stream (a TPU
        # capability beyond the reference; ops/multivariate.py). Absent block
        # or "enabled": false disables; an empty {} means enabled-with-defaults.
        "multivariateDetector": {
            "enabled": False,
            "alpha": 0.05,  # EW smoothing factor for mean/covariance
            "threshold": 3.0,  # signal at normalized Mahalanobis > threshold
            "warmup": 22,  # polls before a host may signal; keep >= 2x feature count
            "influence": 0.25,  # damping for signalling samples (1 = none)
            # baseline snapshot (None disables); avoids a full re-warmup
            # (~warmup polls of blindness) on every module restart
            "resumeFileFullPath": None,
            "resumeFileSaveFrequencyInSeconds": 60,
        },
    },
    "grafana": {
        "grafanaURL": "",
        "grafanaHostname": "",
        "alertInspectorRelativeURL": "/d/alert-inspector",
        "grafanaNowDelayIntervalMs": 90000,
        "bearerToken": "",
        "renderDir": "renders",
        "renderWidth": 1800,
        "renderHeightMultiple": 750,
        "renderExtraParams": "&autofitpanels",
        "renderTimeout": 90000,
    },
    # Pod-scale sharded serving spine (parallel/fleet.py, DESIGN.md §10):
    # shards > 0 switches the producer side to service-hash partitioning
    # (the `transactions` queue becomes one `transactions.p<K>` channel per
    # partition, partition id stamped in headers) and the worker side to
    # per-partition epoch cycles — each shard process (identity from
    # APM_SHARD_ID, or fleet.shardId for embedders) consumes the partition
    # queues it owns with a per-queue dedup window and its own delta chain.
    # partitionKey picks the stable-hash routing key field of a tx line
    # ("service" | "server"). epochStallSeconds: a shard that has intake
    # (unacked/pending) but no committed epoch for this long reports
    # healthz 503 (the manager /fleet plane degrades with it). Rebalance
    # is the quiesced handoff verified by analysis/protocol/shardmodel.py:
    # ownership of a partition moves only with an empty unacked ledger and
    # carries the partition queue's dedup-window ids + the partition's
    # state rows (WorkerApp.release_partition / adopt_partition).
    # partitions decouples the keyspace grain from the process count
    # (P >= N): 0 means auto (4x shards, min 1 per shard), so a rebalance
    # moves a fine slice instead of half a shard's keyspace. Boot
    # ownership is striped (partition p -> shard p % N). controlDir, when
    # set, makes each fleet worker poll a durable per-shard control file
    # (shard<k>.ctl.json, tmp+rename, seq-numbered) for release/adopt
    # commands — the channel the rebalance controller drives; commands
    # survive kill -9 of either side and are re-executed on restart.
    # rebalance.* is the automatic controller policy (parallel/
    # rebalancer.py, pre-verified as a transition system in
    # analysis/protocol/shardmodel.py policy mode): enabled freezes/
    # unfreezes the whole plane (moves stop, observation continues);
    # highWatermark/lowWatermark bound donor/recipient lag (messages) for
    # a move to qualify; the hysteresis band requires the donor-recipient
    # gap to STRICTLY exceed the moved partition's lag; cooldownSeconds
    # enforces at most one move per window (the anti-storm clause);
    # movesPerPartition is the per-partition budget between touches (the
    # anti-oscillation clause); intervalSeconds is the observe/decide
    # cadence; moveTimeoutSeconds bounds one release->adopt handoff
    # before the controller aborts it (the releaser re-adopts its own
    # export).
    "fleet": {
        "shards": 0,
        "partitions": 0,
        "partitionKey": "service",
        "shardId": None,
        "epochStallSeconds": 300.0,
        "controlDir": None,
        "rebalance": {
            "enabled": False,
            "highWatermark": 64,
            "lowWatermark": 16,
            "cooldownSeconds": 30.0,
            "movesPerPartition": 1,
            "intervalSeconds": 5.0,
            "moveTimeoutSeconds": 60.0,
        },
    },
    # TPU-native engine settings (no reference equivalent: this is the device
    # configuration for the batched step function that replaces the per-message
    # stream_calc_stats/z_score/process_alerts event loops).
    "tpuEngine": {
        "logFilePrefix": "tpu_worker",
        "serviceCapacity": 1024,  # static [S] rows; grows by power-of-2 recompile
        "samplesPerBucket": 128,  # per-key per-bucket elapsed sample capacity
        "meshAxis": "services",
        "dtype": "float32",
        # Storage dtype of the z-score lag rings — the engine's dominant HBM
        # buffer. "bfloat16" halves that read traffic per tick (statistics
        # still accumulate in `dtype`; ~0.4% relative rounding on stored
        # values). "" / unset = same as `dtype`.
        "zscoreRingDtype": "",
        # HBM watchdog (device-side analog of the manager's RSS watchdog):
        # manager-alert when bytes_in_use/bytes_limit crosses this fraction
        "deviceMemoryAlarmFraction": 0.9,
        # z-score window variance: "auto" (one ring pass via shifted sumsq in
        # f32 — ~1.4x the dominant reduce, <=1e-5 relative var error; f64
        # parity mode always keeps the exact two-pass), "one", or "two".
        "zscoreVariancePass": "auto",
        "checkpointDir": "save/tpu_engine",
        "resumeFileFullPath": "save/tpu_engine.resume.npz",
        # Checkpoint representation (DESIGN.md §7.4): "full" = one atomic
        # npz snapshot per save (state-size-proportional, the pre-delta
        # behavior); "delta" = incremental delta-chain commits under
        # checkpointChainDir — each epoch appends only the rows/columns
        # touched since the last commit (ingest-rate-proportional, the
        # sub-second-epoch mode), with a full-snapshot compaction rewritten
        # off the hot path every checkpointCompactEveryEpochs commits.
        # checkpointFsync hardens segment/manifest renames against power
        # loss (SIGKILL safety needs only the atomic rename). Write failures
        # (ENOSPC/EIO) retry with decorrelated jitter between
        # checkpointWriteRetryBaseSeconds and checkpointWriteRetryMaxSeconds;
        # after checkpointWriteMaxRetries consecutive failures the worker
        # degrades: flight bundle, operator alert, intake paused until a
        # write lands (healthz 503, apm_checkpoint_degraded). In fleet mode
        # (fleet.shards > 0) checkpointChainDir / resumeFileFullPath /
        # protocolEventLog may carry a "{shard}" placeholder, substituted
        # with the shard id so N shards of one shared config file get
        # disjoint chains (per-shard chain dirs are the handoff unit the
        # rebalance protocol moves ownership between).
        "checkpointMode": "full",
        "checkpointChainDir": "save/tpu_engine.chain",
        "checkpointCompactEveryEpochs": 64,
        "checkpointFsync": True,
        "checkpointWriteMaxRetries": 5,
        "checkpointWriteRetryBaseSeconds": 0.5,
        "checkpointWriteRetryMaxSeconds": 30.0,
        "microBatchSize": 65536,
        # Tick executor selection (DESIGN.md §1): "auto" size-gates the fused
        # single-dispatch program vs the staged pipeline; force with
        # "fused"/"staged". percentileImpl "auto" uses the native radix/
        # nth_element host kernel when the toolchain built it ("native"/
        # "device" force). zscoreRebuildEvery is the staggered sliding-agg
        # rebuild cadence in ticks.
        "tickExecutor": "auto",
        "percentileImpl": "auto",
        "zscoreRebuildEvery": 64,
        # host intake: C++ TxDecoder batch CSV decode (nativeDecode), SPSC
        # byte ring between transport consumer and device loop
        # (useNativeRing/ringBytes), bounded Python-list overflow when the
        # ring is full (intakeOverflowMaxLines after blocking up to
        # ringFullMaxBlockSeconds)
        "nativeDecode": True,
        "useNativeRing": True,
        "ringBytes": 4194304,
        "intakeOverflowMaxLines": 200000,
        "ringFullMaxBlockSeconds": 2.0,
        # frame intake (transport.frameMode producers): True decodes APF1
        # frame batches straight into the columnar ingest path
        # (PipelineDriver.feed_frames — no per-line Python); False unfolds
        # each batch back into lines at the feed boundary (compat path,
        # same records either way)
        "feedFrames": True,
        # double-buffered emission overlap (catch-up aware; r6)
        "asyncEmission": False,
        # per-module profiling harness keys (honored in EVERY module section,
        # like metricsPort; listed once here for the schema): SIGUSR2 /
        # MemoryError heap snapshots into heapSnapshotDir, optional JAX
        # profiler server on profilerPort, tracemalloc via traceAllocations
        "heapSnapshotDir": "logs",
        "profilerPort": None,
        "traceAllocations": False,
        # Delivery guarantee (DESIGN.md §7): "atMostOnce" = reference parity,
        # ack on receipt, in-flight loss bounded by the resume cadence.
        # "atLeastOnce" = manual acks committed only after the engine
        # checkpoint that absorbed them (epoch cycle, runtime/worker.py);
        # redeliveries deduped by msg_id against a window of this many
        # recently absorbed ids persisted inside every snapshot.
        "deliveryMode": "atMostOnce",
        "dedupWindowSize": 65536,
        # Protocol event log (DESIGN.md §9.4 trace conformance): when set
        # to a path, the worker appends one JSONL event per protocol step
        # (recover/deliver/feed/checkpoint/ack/compact); the model
        # checker's conformance tier replays the log as a path of the
        # at-least-once + delta-chain models. Off in production unless a
        # protocol flight log is wanted — cost is one json.dumps + write
        # per delivery.
        "protocolEventLog": None,
        # at-least-once intake batching: accepted deliveries buffer up to
        # this many lines and reach the engine as one bulk feed (the native
        # CSV decode path) instead of per-message object feeds; drained on
        # batch-full, on deliveryFeedMaxDelaySeconds, and always before an
        # epoch checkpoint (token<->effect alignment preserved).
        "deliveryBatchSize": 256,
        "deliveryFeedMaxDelaySeconds": 0.25,
        # mirror StatEntry/FullStatEntry lines onto the reference's 'stats' /
        # 'z_score' queues for per-stage inspection and interop (SURVEY.md §4)
        "emitStatsQueue": False,
        "emitZScoreQueue": False,
        "metricsPort": None,  # telemetry exporter port (0 = ephemeral)
        # Multi-window EWMA/seasonal baselining channels beside the lag
        # windows (no reference equivalent; SURVEY.md §7.2 step 10). Keys are
        # uppercase like streamCalcZScore.defaults. SEASON_SLOTS=24 +
        # SLOT_INTERVALS=360 keeps one baseline per UTC hour-of-day at the
        # stock 10 s cadence; CHANNEL_ID is the (negative) wire 'lag';
        # TREND_BETA > 0 upgrades the channel to a Holt (level+trend)
        # baseline that tracks legitimately-ramping services instead of
        # letting the flat EWMA's variance inflate around the ramp residual
        # and mask real regressions.
        "ewmaChannels": [],
        # Per-service THRESHOLD/INFLUENCE overrides for the EWMA-family
        # channels, keyed service -> channel id (the streamCalcZScore
        # .overrides shape extended to these channels):
        #   {"services": {"getOffers": {"-1": {"THRESHOLD": 2.0}}}}
        "ewmaChannelOverrides": {"services": {}},
    },
}


def main(argv=None) -> int:
    """``python -m apmbackend_tpu config [path]``: print (or write) the full
    default config as ``//``-commented JSON — the starting point a reference
    deployment edits, schema-compatible with its apm_config.json."""
    import argparse
    import sys

    ap = argparse.ArgumentParser(prog="apmbackend_tpu config")
    ap.add_argument("path", nargs="?", help="write here instead of stdout")
    args = ap.parse_args(argv)
    text = (
        "// apmbackend_tpu configuration (apm_config.json schema).\n"
        "// JSON with //-comment lines; hot-reloaded with debounce while the\n"
        "// pipeline runs. TPU-engine settings live under \"tpuEngine\".\n"
        + json.dumps(default_config(), indent=2)
        + "\n"
    )
    if args.path:
        with open(args.path, "w", encoding="utf-8") as fh:
            fh.write(text)
    else:
        sys.stdout.write(text)
    return 0
