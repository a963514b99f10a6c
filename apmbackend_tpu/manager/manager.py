"""The supervisor process (apm_manager.js role).

Forks every configured module as a detached child (stdout/stderr to
``<name>.start.log``), restarts on exit with crash-loop damping, polls each
child's PSS/swap and requests GC (SIGUSR1) over threshold, watches disk space,
queue depth/memory and the broker's liveness, prunes old logs, batches its own
operational alerts into emails with interval doubling, and posts Grafana
``maintenance`` annotations around restarts.

Differences from the reference, by design:

- children are ``python -m <module>`` (moduleSettings[].module), matched for
  stale-PID cleanup by cmdline regex instead of ps output parsing;
- ``requestGC`` rides SIGUSR1 (ModuleRuntime installs the handler) instead of
  a Node IPC channel (apm_manager.js:505-509 -> util_methods.js:463-467);
- broker supervision is backend-aware: for AMQP it shells to rabbitmqctl like
  the reference (gated on the binary existing); the in-process memory broker
  needs no supervision.
"""

from __future__ import annotations

import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from ..integrations import EmailSender, GrafanaClient
from ..utils.counters import capped_append

# The CLI dispatcher (`python -m apmbackend_tpu <cmd>`) runs the same modules
# with a different /proc cmdline than `python -m <dotted.module>`; stale-PID
# matching must catch both or two supervisors can fight over children. The
# alias map is derived from the dispatcher's own command table so the two
# cannot drift.
def _dispatch_aliases() -> dict:
    from apmbackend_tpu.__main__ import COMMANDS

    return {module: cmd for cmd, (module, _takes_argv) in COMMANDS.items()}


_DISPATCH_ALIASES = _dispatch_aliases()


def cmdline_pattern_for(module: str) -> str:
    """Regex matching both launch forms of a module process."""
    pats = [rf"-m\s+{re.escape(module)}(\s|$)"]
    alias = _DISPATCH_ALIASES.get(module)
    if alias:
        pats.append(rf"-m\s+apmbackend_tpu\s+{alias}(\s|$)")
    return "|".join(f"(?:{p})" for p in pats)


def expand_module_settings(module_settings: List[dict]) -> List[tuple]:
    """Expand every moduleSettings entry into its child processes:
    ``[(setting, extra_env, sweep_stale)]``.

    An entry with ``"shards": N`` (N > 0) becomes N children of the SAME
    module — the pod-scale fleet (DESIGN.md §10): each child gets
    ``APM_SHARD_ID=<k>`` in its environment (the worker derives partition
    ownership and ``{shard}``-templated chain paths from it), a per-shard
    ``name`` (``worker0``..) for logs/metrics/watchdog bookkeeping, and a
    per-shard ``metricsPort`` (base + k) so the /fleet plane scrapes each
    shard separately. Only shard 0 sweeps stale PIDs — the siblings share
    one cmdline pattern and must not SIGTERM each other at boot."""
    out = []
    for ms in module_settings:
        shards = int(ms.get("shards", 0) or 0)
        if shards <= 0:
            out.append((ms, {}, True))
            continue
        base_name = ms.get("name") or ms["module"].rsplit(".", 1)[-1]
        base_port = ms.get("metricsPort")
        for k in range(shards):
            child = dict(ms)
            child["name"] = f"{base_name}{k}"
            env = {"APM_SHARD_ID": str(k)}
            if base_port:
                # shards share one config file, so the per-shard exporter
                # port rides the environment (ModuleRuntime honors
                # APM_METRICS_PORT over the config section's metricsPort)
                child["metricsPort"] = int(base_port) + k
                env["APM_METRICS_PORT"] = str(int(base_port) + k)
            out.append((child, env, k == 0))
    return out


class ManagerAlerts:
    """Operational alert batching with interval doubling
    (apm_manager.js:42-132). Buffers plain strings, emails them joined."""

    MAX_BUFFERED = 1000  # drop-oldest cap: alerts accrue forever when emails
    # are disabled (every inspection cycle can add), so an unbounded list
    # would leak in a long-lived supervisor

    def __init__(self, manager_config: dict, *, email_sender=None, logger=None):
        self.config = manager_config
        self.email_sender = email_sender
        self.logger = logger
        self.buffer: List[str] = []
        self.dropped = 0
        self._lock = threading.Lock()
        self._timer: Optional[threading.Timer] = None
        self._stopped = False

    def set_config(self, manager_config: dict) -> None:
        self.config = manager_config

    def add(self, message: str) -> None:
        if self.logger:
            self.logger.warning(f"Manager alert: {message}")
        with self._lock:
            self.dropped += capped_append(self.buffer, message, self.MAX_BUFFERED)

    def send_email(self, subject: str, body: str) -> None:
        """Immediate send (sendManagerEmail role), gated on emailsEnabled."""
        if self.email_sender is not None and self.config.get("emailsEnabled"):
            self.email_sender(subject, body.replace("\n", "<br>"), None)

    def flush(self, interval_s: Optional[float] = None) -> tuple:
        base = float(self.config.get("alertCollectionIntervalInSeconds", 60))
        if interval_s is None:
            interval_s = base
        can_send = self.email_sender is not None and bool(self.config.get("emailsEnabled"))
        with self._lock:
            if not self.buffer or not can_send:
                return 0, base
            # take the batch atomically so an add() racing the (slow) send
            # is never wiped by the post-send clear
            batch, self.buffer = self.buffer, []
            dropped, self.dropped = self.dropped, 0
        count = len(batch)
        if self.config.get("increaseCollectionIntervalAfterAlert"):
            # clamp: doubling from a non-power-of-two base must not overshoot
            # the configured cap
            interval_s = min(
                interval_s * 2, float(self.config.get("maxCollectionIntervalInSeconds", 3840))
            )
        if dropped:
            batch.insert(0, f"({dropped} older alerts dropped at the {self.MAX_BUFFERED}-entry cap)")
        html = "<br>\n".join(batch)
        self.email_sender("APM manager alerts", html, None)
        return count, interval_s

    def start(self) -> None:
        """Recursion with per-flush interval (startAlertSender role)."""

        def _fire(interval_s: float):
            if self._stopped:
                return
            try:
                _count, next_interval = self.flush(interval_s)
            except Exception as e:
                if self.logger:
                    self.logger.error(f"Manager alert flush error: {e}")
                next_interval = interval_s
            self._timer = threading.Timer(next_interval, _fire, args=(next_interval,))
            self._timer.daemon = True
            self._timer.start()

        base = float(self.config.get("alertCollectionIntervalInSeconds", 60))
        self._timer = threading.Timer(base, _fire, args=(base,))
        self._timer.daemon = True
        self._timer.start()

    def stop(self) -> None:
        self._stopped = True
        if self._timer is not None:
            self._timer.cancel()


class ModuleProc:
    """One supervised child module (Module class role, apm_manager.js:246-357)."""

    def __init__(
        self,
        module_setting: dict,
        *,
        log_dir: str,
        config_path: Optional[str],
        logger=None,
        on_exit_alert: Optional[Callable[[str, str], None]] = None,
        clock: Callable[[], float] = time.monotonic,
        python: str = sys.executable,
        extra_env: Optional[dict] = None,
        sweep_stale: bool = True,
    ):
        self.module = module_setting["module"]  # e.g. "apmbackend_tpu.runtime.worker"
        self.setting = module_setting
        self.log_dir = log_dir
        self.config_path = config_path
        self.logger = logger
        self.on_exit_alert = on_exit_alert
        self.clock = clock
        self.python = python
        self.extra_env = extra_env or {}
        # shard siblings share one cmdline pattern: only the designated
        # sweeper (shard 0) may kill stale pids, or N shards would
        # SIGTERM each other at boot (expand_module_settings)
        self.sweep_stale = sweep_stale
        self.proc: Optional[subprocess.Popen] = None
        self.last_start_time: float = 0.0
        self.restart_pending_until: float = 0.0

    @property
    def name(self) -> str:
        # fleet shards override the name (worker0, worker1, ...) so log
        # files, metrics relabeling, and watchdog streaks stay per-shard
        return self.setting.get("name") or self.module.rsplit(".", 1)[-1]

    @property
    def pid(self) -> Optional[int]:
        return self.proc.pid if self.proc is not None else None

    def cmdline_pattern(self) -> str:
        return cmdline_pattern_for(self.module)

    def kill_existing_pids(self) -> int:
        """Stale-PID cleanup before forking (killExistingPIDs role)."""
        from .pid_stats import pid_exists, pids_matching_cmdline

        if not self.sweep_stale:
            return 0
        killed = 0
        for pid in pids_matching_cmdline(self.cmdline_pattern()):
            try:
                os.kill(pid, signal.SIGTERM)
                killed += 1
                if self.logger:
                    self.logger.warning(f"Process PID: {pid} has been killed intentionally ({self.module})")
            except OSError as e:
                if self.logger:
                    self.logger.error(f"Could not kill pid: {pid} Error: {e}")
        if killed:
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline and any(
                pid_exists(p) for p in pids_matching_cmdline(self.cmdline_pattern())
            ):
                time.sleep(0.1)
            for pid in pids_matching_cmdline(self.cmdline_pattern()):
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass
        return killed

    def start_process(self) -> None:
        os.makedirs(self.log_dir, exist_ok=True)
        out_path = os.path.join(self.log_dir, f"{self.name}.start.log")
        # append: a restart must not truncate the crash output that caused it
        out_fd = open(out_path, "a")
        self.last_start_time = self.clock()
        env = dict(os.environ, **self.extra_env)
        if self.config_path:
            env["APM_CONFIG"] = self.config_path
        self.proc = subprocess.Popen(
            [self.python, "-m", self.module],
            stdin=subprocess.DEVNULL,
            stdout=out_fd,
            stderr=out_fd,
            start_new_session=True,  # detached (fork {detached: true} role)
            env=env,
        )
        out_fd.close()
        if self.logger:
            self.logger.info(f"Child process started via PID: {self.proc.pid} ({self.module})")

    def poll_exit(self) -> Optional[int]:
        """Non-blocking: return the exit code if the child exited, else None."""
        if self.proc is None:
            return None
        return self.proc.poll()

    def handle_exit(self, code: int) -> None:
        """Crash-loop damping: exited <5 s after start => wait 60 s before the
        restart, else 1 s (childExitCB, apm_manager.js:303-327). Non-blocking:
        the restart fires once the damping window elapses (see tick())."""
        if self.on_exit_alert:
            self.on_exit_alert(
                "APM manager error",
                f"Child module exited: code:{code} module: {self.module}",
            )
        now = self.clock()
        delay = 60.0 if (now - self.last_start_time) < 5.0 else 1.0
        if self.logger and delay > 1.0:
            self.logger.warning(
                "Time since last restart is under 5 seconds, something is likely "
                "wrong with the module (not a one-off kill); damping restart 60s"
            )
        self.proc = None
        self.restart_pending_until = now + delay

    def tick(self) -> Optional[str]:
        """Periodic state machine step; returns an event string when something
        happened ('exited', 'restarted')."""
        if self.proc is not None:
            code = self.poll_exit()
            if code is not None:
                self.handle_exit(code)
                return "exited"
            return None
        if self.restart_pending_until and self.clock() >= self.restart_pending_until:
            self.restart_pending_until = 0.0
            self.start_process()
            return "restarted"
        return None

    def request_gc(self) -> None:
        if self.pid is not None and hasattr(signal, "SIGUSR1"):
            try:
                os.kill(self.pid, signal.SIGUSR1)
            except OSError:
                pass

    def force_restart(self, *, kill_timeout_s: float = 10.0) -> None:
        """Kill a wedged-but-alive child and route it through the SAME
        crash-loop-damped restart path a self-exit takes (handle_exit): a
        child that wedges immediately after every restart gets the 60 s
        damping instead of a tight kill/restart loop."""
        if self.proc is None:
            return
        try:
            self.proc.terminate()
            self.proc.wait(timeout=kill_timeout_s)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            try:
                self.proc.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                pass
        code = self.proc.returncode if self.proc.returncode is not None else -9
        self.handle_exit(code)

    def stop(self, *, kill_timeout_s: float = 10.0) -> None:
        if self.proc is None:
            return
        try:
            self.proc.terminate()
            self.proc.wait(timeout=kill_timeout_s)
        except subprocess.TimeoutExpired:
            self.proc.kill()
        self.proc = None


class ManagerApp:
    """The supervisor main loop, wired onto a ModuleRuntime for config/logging."""

    def __init__(self, runtime, *, spawn_children: bool = True):
        self.runtime = runtime
        config = runtime.config
        self.mconfig = runtime.module_config
        logger = runtime.logger

        email_sender = None
        if self.mconfig.get("emailsEnabled"):
            email_sender = EmailSender(
                self.mconfig.get("fromEmail", "apm@localhost"),
                self.mconfig.get("emailList", ""),
                logger=logger,
            )
        grafana_cfg = config.get("grafana", {})
        self.grafana = GrafanaClient(grafana_cfg, logger=logger) if grafana_cfg.get("grafanaURL") else None
        self.alerts = ManagerAlerts(self.mconfig, email_sender=email_sender, logger=logger)

        self.modules: List[ModuleProc] = [
            ModuleProc(
                ms,
                log_dir=config.get("logDir", "logs"),
                config_path=runtime.config_path,
                logger=logger,
                on_exit_alert=self._on_child_exit_alert,
                extra_env=env,
                sweep_stale=sweep,
            )
            for ms, env, sweep in expand_module_settings(
                self.mconfig.get("moduleSettings", [])
            )
        ]

        # -- telemetry: restart/GC/exit event counters + the fleet scrape ----
        # Counters exist regardless of an exporter (they also feed /healthz);
        # the /fleet route mounts only when the manager runtime serves one.
        from ..obs import get_registry

        reg = get_registry()
        # keyed by mod.name (not module path): fleet shards share one
        # module path but are independent children with their own counters
        self._m_restarts = {
            mod.name: reg.counter(
                "apm_manager_child_restarts_total",
                "Child module restarts by the supervisor",
                labels={"module": mod.name},
            )
            for mod in self.modules
        }
        self._m_exits = {
            mod.name: reg.counter(
                "apm_manager_child_exits_total",
                "Child module exits observed by the supervisor",
                labels={"module": mod.name},
            )
            for mod in self.modules
        }
        self._m_gcs = {
            mod.name: reg.counter(
                "apm_manager_gc_requests_total",
                "GC requests (SIGUSR1) sent to the child",
                labels={"module": mod.name},
            )
            for mod in self.modules
        }
        self._m_watchdog = {
            mod.name: reg.counter(
                "apm_manager_watchdog_restarts_total",
                "Wedged-but-alive children force-restarted by the healthz watchdog",
                labels={"module": mod.name},
            )
            for mod in self.modules
        }
        # hung-tick watchdog bookkeeping: consecutive failed /healthz probes
        # per child (reset on success, on restart, and while no process)
        self._health_streaks = {mod.name: 0 for mod in self.modules}
        if getattr(runtime, "telemetry", None) is not None:
            runtime.telemetry.add_route("/fleet", self._fleet_route)
            runtime.telemetry.add_health("fleet", self._fleet_health)

        # -- durable telemetry spine (obs/store + recorder + SLO, §8.4) ------
        # observability.recorderDir turns on the fleet recorder: every
        # child's /metrics, /trace, /decisions persisted shard-labeled each
        # recorderIntervalSeconds, so a kill−9'd child's telemetry survives
        # into triage; the SLO engine burns error budgets over that store,
        # pages through ManagerAlerts, and degrades /healthz on fast burn.
        self.recorder = None
        self.recorder_store = None
        self.slo = None
        obs_cfg = config.get("observability", {})
        recorder_dir = obs_cfg.get("recorderDir")
        if recorder_dir:
            from ..obs.recorder import FleetRecorder
            from ..obs.slo import SLOEngine
            from ..obs.store import TimeSeriesStore, make_query_route

            self.recorder_store = TimeSeriesStore(
                str(recorder_dir),
                retention_s=float(obs_cfg.get("recorderRetentionSeconds", 3600.0)),
                downsample_after_s=obs_cfg.get("recorderDownsampleAfterSeconds", 900.0),
                downsample_step_s=float(obs_cfg.get("recorderDownsampleStepSeconds", 60.0)),
                registry=reg,
                logger=logger,
            )
            self.recorder = FleetRecorder(
                self.recorder_store,
                self._child_metrics_targets,
                interval_s=float(obs_cfg.get("recorderIntervalSeconds", 2.0)),
                self_registry=reg,
                registry=reg,
                logger=logger,
            )
            runtime.every(
                max(0.05, self.recorder.interval_s),
                self.recorder.scrape_once,
                name="recorder",
            )
            slo_cfg = config.get("slo", {})
            if bool(slo_cfg.get("enabled", True)):
                self.slo = SLOEngine.from_config(
                    self.recorder_store,
                    config,
                    on_alert=lambda msg, _rec: self.alerts.add(msg),
                    registry=reg,
                    logger=logger,
                )
                runtime.every(
                    max(0.05, float(slo_cfg.get("evaluationIntervalSeconds", 10.0))),
                    self.slo.evaluate,
                    name="slo-eval",
                )
            if getattr(runtime, "telemetry", None) is not None:
                # overrides the per-module /query: range queries here answer
                # over EVERY child's persisted telemetry, dead shards included
                runtime.telemetry.add_route(
                    "/query", make_query_route(lambda: self.recorder_store))
                if self.slo is not None:
                    runtime.telemetry.add_health("slo", self.slo.health)
            if getattr(runtime, "flight", None) is not None:
                runtime.flight.add_source("recorder", self.recorder.status)
                runtime.flight.add_source(
                    "recorder_tail", lambda: self.recorder_store.tail(32))
                if self.slo is not None:
                    runtime.flight.add_source("slo", lambda: self.slo.status())

        # -- ISSUE 18: the self-managing fleet (automatic rebalance) ---------
        # fleet.rebalance.enabled + fleet.controlDir turn the supervisor
        # into the rebalance controller: observe per-partition lag off the
        # shard scrapes (plus SLO fast-burn state), run the pure watermark
        # policy, and execute at most one verified release→adopt move per
        # cooldown window through the durable control-file channel. First
        # tick runs recover() — a controller that died mid-move resolves
        # its own wreckage before making new decisions. Freeze switch:
        # set fleet.rebalance.enabled false and reload.
        self.rebalancer = None
        self._rebalance_recovered = False
        fleet_cfg = config.get("fleet", {}) or {}
        rb_cfg = dict(fleet_cfg.get("rebalance", {}) or {})
        ctl_dir = fleet_cfg.get("controlDir")
        shard_mods = self._fleet_shard_modules()
        if bool(rb_cfg.get("enabled")) and ctl_dir and len(shard_mods) >= 2:
            from ..parallel.rebalancer import CtlPeer, RebalanceController

            os.makedirs(str(ctl_dir), exist_ok=True)
            peers = {
                k: CtlPeer(
                    os.path.join(str(ctl_dir), f"shard{k}.ctl.json"),
                    alive=(lambda m: lambda: m.proc is not None
                           and m.proc.poll() is None)(mod),
                )
                for k, mod in shard_mods.items()
            }
            self.rebalancer = RebalanceController(
                str(ctl_dir), peers, self._rebalance_observation, rb_cfg,
                logger=logger,
            )
            reg.add_collector(self.rebalancer.collect_metrics)
            runtime.every(
                max(0.1, float(rb_cfg.get("intervalSeconds", 5.0))),
                self._rebalance_tick, name="rebalance",
            )
            if getattr(runtime, "flight", None) is not None:
                runtime.flight.add_source(
                    "rebalance",
                    lambda: {"moves": self.rebalancer.moves_total,
                             "aborts": self.rebalancer.aborts_total,
                             "skipped_cooldown":
                                 self.rebalancer.skipped_cooldown_total,
                             "stale_gc":
                                 self.rebalancer.stale_handoffs_gc_total})

        # -- ISSUE 20: the fleet query plane (the read front door) -----------
        # queryPlane.enabled mounts obs.queryplane over this exporter,
        # REPLACING the per-process /query /trace /decisions /attrib:
        # single-service queries route to the owning shard via the pinned
        # hash + the owner map re-derived from shard scrapes; cross-service
        # queries scatter-gather; dead shards are served from the recorder
        # store with partial/stale marking. /attrib and /trace fold in the
        # manager's own plane and span ring (colocated producers) and stitch
        # spans across children by trace_id.
        self.queryplane = None
        qp_cfg = config.get("queryPlane", {}) or {}
        if bool(qp_cfg.get("enabled", True)) \
                and getattr(runtime, "telemetry", None) is not None:
            from ..obs.attrib import get_attrib
            from ..obs.queryplane import QueryPlane
            from ..obs.trace import get_tracer

            qp_partitions = 0
            if shard_mods:
                from ..parallel.fleet import OwnerMap, resolve_partitions

                qp_partitions = resolve_partitions(
                    len(shard_mods), int(fleet_cfg.get("partitions", 0) or 0))
                self._owner_map = OwnerMap()
                self._owner_lock = threading.Lock()
                self._owner_read_ts = 0.0  # guarded-by: _owner_lock
                self._owner_refresh_s = float(
                    qp_cfg.get("ownerRefreshSeconds", 5.0))
            self.queryplane = QueryPlane(
                self._child_metrics_targets,
                owners=self._queryplane_owners if shard_mods else None,
                store=self.recorder_store,
                partitions=qp_partitions,
                partition_key=str(fleet_cfg.get("partitionKey", "service")),
                registry=reg,
                cache_ttl_s=float(qp_cfg.get("cacheTtlSeconds", 2.0)),
                fanout=int(qp_cfg.get("fanoutConcurrency", 8)),
                timeout_s=float(qp_cfg.get("timeoutSeconds", 2.0)),
                move_retries=int(qp_cfg.get("moveRetries", 2)),
                freshness=(self.recorder.freshness
                           if self.recorder is not None else None),
                local_attrib=lambda: get_attrib().snapshot(),
                local_spans=lambda trace_id, n: get_tracer().ring.spans(
                    trace_id=trace_id, n=n),
                logger=logger,
            )
            for qp_path, qp_fn in self.queryplane.make_routes().items():
                runtime.telemetry.add_route(qp_path, qp_fn)
            runtime.telemetry.add_health("queryplane", self.queryplane.health)

        if spawn_children:
            self.annotate("Restarting all modules")
            for mod in self.modules:
                mod.kill_existing_pids()
            for mod in self.modules:
                mod.start_process()

        self.alerts.start()
        freq = int(self.mconfig.get("inspectionFrequencySeconds", 60))
        runtime.every(freq, self.inspect_all, name="monitor", align=True)
        runtime.every(12 * 3600, self.cleanup_logs, name="log-gc")
        runtime.every(1.0, self.tick_modules, name="module-ticker")
        runtime.on_reload(self._apply_config)
        runtime.on_exit(self.shutdown)

    # -- callbacks -----------------------------------------------------------
    def _on_child_exit_alert(self, subject: str, body: str) -> None:
        self.annotate(body)
        self.alerts.send_email(subject, body)
        self.alerts.add(body)

    def annotate(self, text: str) -> None:
        if self.grafana is not None:
            self.grafana.post_annotation(text, ["maintenance"])

    def _apply_config(self, new_config: dict) -> None:
        self.mconfig = new_config.get("applicationManager", {})
        self.alerts.set_config(self.mconfig)
        # emailsEnabled may be switched on at runtime: build the sender the
        # startup path skipped (and refresh addresses on change)
        if self.mconfig.get("emailsEnabled"):
            self.alerts.email_sender = EmailSender(
                self.mconfig.get("fromEmail", "apm@localhost"),
                self.mconfig.get("emailList", ""),
                logger=self.runtime.logger,
            )

    # -- module supervision ---------------------------------------------------
    def tick_modules(self) -> None:
        for mod in self.modules:
            event = mod.tick()
            if event == "restarted":
                self._m_restarts[mod.name].inc()
                self.alerts.send_email(
                    "APM manager alert", f"Process restarted via startProcess: {mod.module}"
                )
            elif event == "exited":
                self._m_exits[mod.name].inc()

    def module_setting(self, mod: ModuleProc, name: str):
        """Per-module override falling back to the manager default
        (getModuleSetting, apm_manager.js:455-464)."""
        if name in mod.setting:
            return mod.setting[name]
        return self.mconfig.get(name)

    def inspect_modules(self) -> None:
        from .pid_stats import pid_exists, pss_swap_mb

        for mod in self.modules:
            if mod.pid is None:
                continue  # restart already pending via tick()
            if not pid_exists(mod.pid):
                mod.tick()  # reap + schedule restart
                continue
            mem, swap = pss_swap_mb(mod.pid)
            if mem is None:
                continue
            trigger_gc = False
            mem_thr_raw = self.module_setting(mod, "moduleMemoryAlertThreshold")
            mem_thr = 350.0 if mem_thr_raw is None else float(mem_thr_raw)
            if mem > mem_thr:
                self.alerts.add(
                    f"Child module exceeded the memory threshold - Module: {mod.module} "
                    f"Threshold(Mb): {mem_thr} MemoryUsed(Mb): {mem:.1f}"
                )
                trigger_gc = True
            swap_thr_raw = self.module_setting(mod, "moduleSwapAlertThreshold")
            swap_thr = 200.0 if swap_thr_raw is None else float(swap_thr_raw)
            if swap > swap_thr:
                self.alerts.add(
                    f"Child module exceeded the swap threshold - Module: {mod.module} "
                    f"Threshold(Mb): {swap_thr} SwapUsed(Mb): {swap:.1f}"
                )
                trigger_gc = True
            if trigger_gc:
                self.runtime.logger.info(f"Sending garbage collection request to module: {mod.module}")
                self._m_gcs[mod.name].inc()
                mod.request_gc()

    def _probe_child_health(self, url: str, timeout_s: float) -> bool:
        """One /healthz probe; True = healthy (HTTP 200). 503, timeout, or a
        refused connection all count as unhealthy. Separate method so tests
        inject probe outcomes without an HTTP server."""
        import urllib.error
        import urllib.request

        try:
            with urllib.request.urlopen(f"{url}/healthz", timeout=timeout_s) as resp:
                return resp.status == 200
        except Exception:
            return False

    def inspect_module_health(self) -> None:
        """Hung-tick watchdog: a child that is ALIVE but answers /healthz
        with a sustained 503/timeout streak is force-restarted through the
        crash-loop-damped path. A dead device loop (or wedged tick thread)
        leaves the process running — poll_exit never fires — so without this
        probe a wedged child consumes its queue's messages never again."""
        threshold = int(self.mconfig.get("healthzFailureThreshold", 3) or 0)
        if threshold <= 0:
            return
        timeout_s = float(self.mconfig.get("healthzTimeoutSeconds", 2))
        targets = dict(self._child_metrics_targets())
        from .pid_stats import pid_exists

        for mod in self.modules:
            url = targets.get(mod.name)
            if url is None or mod.pid is None or not pid_exists(mod.pid):
                self._health_streaks[mod.name] = 0  # exit path handles it
                continue
            if self._probe_child_health(url, timeout_s):
                self._health_streaks[mod.name] = 0
                continue
            self._health_streaks[mod.name] += 1
            streak = self._health_streaks[mod.name]
            if streak < threshold:
                continue
            self._health_streaks[mod.name] = 0
            msg = (
                f"Child module wedged (healthz failed {streak} consecutive "
                f"inspections) - restarting through damped path: {mod.module}"
            )
            self.annotate(msg)
            self.alerts.add(msg)
            self._m_watchdog[mod.name].inc()
            # last-words pull: a wedged-but-serving child can still dump a
            # flight bundle — request one before the SIGTERM destroys the
            # evidence (best effort; a fully dead HTTP thread just times out)
            self._request_child_flight(url, timeout_s)
            mod.force_restart()

    def _request_child_flight(self, url: str, timeout_s: float) -> Optional[str]:
        """GET <child>/flight?reason=watchdog_restart; returns the bundle
        path the child reported, or None. Separate method for test seams."""
        import json as _json
        import urllib.request

        try:
            with urllib.request.urlopen(
                f"{url}/flight?reason=watchdog_restart", timeout=timeout_s
            ) as resp:
                body = _json.loads(resp.read().decode("utf-8", "replace"))
            bundle = body.get("bundle")
            if bundle:
                self.runtime.logger.warning(f"Wedged child flight bundle: {bundle}")
            return bundle
        except Exception:
            return None

    # -- automatic rebalance (ISSUE 18) ---------------------------------------
    def _fleet_shard_modules(self) -> Dict[int, object]:
        """{shard_id: ModuleProc} for the sharded worker children — the
        shard id rides each child's APM_SHARD_ID (expand_module_settings
        stamped it; the worker derived its partition set from it)."""
        out = {}
        for mod in self.modules:
            sid = (mod.extra_env or {}).get("APM_SHARD_ID")
            if sid is not None:
                out[int(sid)] = mod
        return out

    def _shard_scrapes(self, timeout_s: float = 2.0) -> Dict[int, str]:
        """{shard_id: raw /metrics body} for every live shard child. A
        dead shard contributes nothing — its partitions drop out of the
        attribution, which is exactly what the controller must see (it
        cannot move what nobody reports owning)."""
        import urllib.request

        host = str(self.runtime.config.get("observability", {})
                   .get("metricsHost", "127.0.0.1"))
        out = {}
        for k, mod in self._fleet_shard_modules().items():
            port = mod.setting.get("metricsPort")
            if not port:
                continue
            try:
                with urllib.request.urlopen(
                        f"http://{host}:{int(port)}/metrics",
                        timeout=timeout_s) as resp:
                    out[k] = resp.read().decode("utf-8", "replace")
            except Exception:
                pass
        return out

    def _rebalance_observation(self):
        """One controller scrape: per-partition lag + ownership
        attribution off the shard exports (stale TOGETHER — the policy
        model's view+vmap), and the SLO engine's fast-burning partitions
        mapped to their owning shards."""
        from ..obs.slo import burning_partitions
        from ..parallel.rebalancer import observation_from_metrics

        obs = observation_from_metrics(self._shard_scrapes())
        if self.slo is not None:
            burning = burning_partitions(self.slo.status().get("results"))
            obs.burning = {obs.owners[p] for p in burning if p in obs.owners}
        return obs

    def _queryplane_owners(self):
        """The query plane's routing feed: ``(seq, {partition: module
        name})``, re-derived from the shard scrapes' ownership
        attribution at most every ownerRefreshSeconds (routing reads are
        per-request; the scrape is not). OwnerMap bumps the seq only on
        real change, so steady-state rescrapes never force query
        retries; a failed scrape pass keeps serving the last good map."""
        with self._owner_lock:
            now = time.monotonic()
            refresh = now - self._owner_read_ts >= self._owner_refresh_s
            if refresh:
                self._owner_read_ts = now
        if refresh:
            try:
                from ..parallel.rebalancer import observation_from_metrics

                obs = observation_from_metrics(self._shard_scrapes())
                names = {k: mod.name
                         for k, mod in self._fleet_shard_modules().items()}
                self._owner_map.update({
                    p: names[s] for p, s in obs.owners.items() if s in names
                })
            except Exception as e:
                self.runtime.logger.debug(f"owner-map refresh failed: {e}")
        return self._owner_map.read()

    def _rebalance_tick(self) -> None:
        """Timer body: recover leftovers once (retried until it lands —
        shards may still be booting on the first passes), then one
        observe → decide → execute pass. Never raises into the timer."""
        if self.rebalancer is None:
            return
        try:
            if not self._rebalance_recovered:
                self.rebalancer.recover()
                self._rebalance_recovered = True
            self.rebalancer.tick()
        except Exception as e:
            self.runtime.logger.warning(f"rebalance tick failed: {e}")

    # -- fleet telemetry aggregation ------------------------------------------
    def _child_metrics_targets(self) -> List[tuple]:
        """[(name, url)] for children whose moduleSettings carry a
        ``metricsPort`` — the scrape inventory of this supervisor."""
        host = str(self.runtime.config.get("observability", {}).get("metricsHost", "127.0.0.1"))
        out = []
        for mod in self.modules:
            port = mod.setting.get("metricsPort")
            if port:
                out.append((mod.name, f"http://{host}:{int(port)}"))
        return out

    def scrape_fleet(self, timeout_s: float = 2.0) -> str:
        """GET every child's /metrics, stamp ``module=<name>`` into each
        series, and concatenate — one exposition for the whole fleet (what
        the reference's per-dashboard rabbitmqctl/ps scraping becomes). A
        down child contributes an ``apm_fleet_child_up 0`` marker instead of
        failing the whole scrape."""
        import urllib.request

        from ..obs import relabel_metrics

        shard_names = {mod.name: k
                       for k, mod in self._fleet_shard_modules().items()}
        bodies: Dict[int, str] = {}
        parts = []
        for name, url in self._child_metrics_targets():
            up = 1
            try:
                with urllib.request.urlopen(f"{url}/metrics", timeout=timeout_s) as resp:
                    body = resp.read().decode("utf-8", "replace")
                parts.append(relabel_metrics(body, {"module": name}))
                if name in shard_names:
                    bodies[shard_names[name]] = body
            except Exception:
                up = 0
            parts.append(
                f'# TYPE apm_fleet_child_up gauge\napm_fleet_child_up{{module="{name}"}} {up}\n'
            )
        if bodies:
            # the partition -> shard ownership map (ISSUE 18): derived from
            # each shard's apm_partition_lag attribution, so /fleet answers
            # "who serves partition K right now" without a control probe
            from ..parallel.rebalancer import observation_from_metrics

            obs = observation_from_metrics(bodies)
            if obs.owners:
                parts.append("# TYPE apm_fleet_partition_owner gauge\n")
                for p in sorted(obs.owners):
                    parts.append(
                        f'apm_fleet_partition_owner{{partition="{p}"}} '
                        f"{obs.owners[p]}\n")
        return "".join(parts)

    def _fleet_route(self, _query):
        from ..obs.exporter import PROM_CONTENT_TYPE

        return 200, PROM_CONTENT_TYPE, self.scrape_fleet()

    def _fleet_health(self) -> dict:
        """Aggregated child liveness for the manager's own /healthz: process
        up/down per child plus each child's /healthz status when it serves
        one (restart-pending children degrade the fleet)."""
        import json as _json
        import urllib.request

        from .pid_stats import pid_exists

        targets = dict(self._child_metrics_targets())
        children = {}
        ok = True
        for mod in self.modules:
            alive = mod.pid is not None and pid_exists(mod.pid)
            info = {"up": alive, "pid": mod.pid}
            if not alive:
                ok = False
                info["restart_pending"] = bool(mod.restart_pending_until)
            url = targets.get(mod.name)
            if alive and url:
                import urllib.error

                try:
                    with urllib.request.urlopen(f"{url}/healthz", timeout=2.0) as resp:
                        info["healthz"] = _json.loads(resp.read().decode("utf-8")).get("status")
                except urllib.error.HTTPError as e:
                    # a degraded child answers 503 WITH its status body —
                    # parse it rather than flattening to an opaque error
                    try:
                        info["healthz"] = _json.loads(
                            e.read().decode("utf-8")).get("status")
                    except Exception:
                        info["healthz_error"] = repr(e)
                except Exception as e:
                    info["healthz_error"] = repr(e)
                if info.get("healthz", "ok") != "ok" or "healthz_error" in info:
                    # a degraded child degrades the fleet: a shard whose
                    # epoch stalls (or whose checkpoint volume died) answers
                    # 503 with status "degraded", and the manager's own
                    # /healthz must go 503 with it — the fleet is not
                    # serving its SLO while any partition's effects cannot
                    # commit (DESIGN.md §10)
                    ok = False
            children[mod.name] = info
        return {"ok": ok, "children": children}

    # -- host monitors --------------------------------------------------------
    def inspect_disk_space(self) -> None:
        mount = self.mconfig.get("diskInspectionMount") or self.runtime.config.get("appDirectory", ".")
        try:
            usage = shutil.disk_usage(os.path.abspath(mount))
        except OSError as e:
            self.alerts.add(f"Could not inspect mount disk usage: {e}")
            return
        gb = 1024.0 ** 3
        avail_gb = usage.free / gb
        percent = 100.0 * usage.used / usage.total if usage.total else 0.0
        if avail_gb <= float(self.mconfig.get("diskSpaceGBAvailableThreshold", 100)):
            self.alerts.add(
                f"Available disk space is low on mount: {mount} - "
                f"Available: {avail_gb:.1f} GB, PercentUsed: {percent:.0f}%"
            )
        if percent > float(self.mconfig.get("diskSpacePercentageUsedThreshold", 80)):
            self.alerts.add(
                f"Disk space percentage used is high on mount: {mount} - "
                f"Available: {avail_gb:.1f} GB, PercentUsed: {percent:.0f}%"
            )

    def inspect_queues(self) -> None:
        """Depth/memory thresholds over every queue (apm_manager.js:429-453)."""
        rows = self._queue_rows()
        if rows is None:
            return
        msg_thr = int(self.mconfig.get("queueMessageAlertThreshold", 1000000))
        mem_thr = float(self.mconfig.get("queueMemoryAlertThreshold", 150))
        for name, count, mem_mb in rows:
            if count > msg_thr:
                self.alerts.add(
                    f"Queue exceeded the message count threshold - Queue: {name} "
                    f"Threshold: {msg_thr} MessageCount: {count}"
                )
            if mem_mb == mem_mb and mem_mb > mem_thr:
                self.alerts.add(
                    f"Queue exceeded the memory threshold - Queue: {name} "
                    f"Threshold: {mem_thr} MemoryUsed(Mb): {mem_mb:.1f}"
                )

    def _queue_rows(self):  # pragma: no cover - requires rabbitmqctl
        if self.runtime.config.get("brokerBackend") != "amqp":
            return None
        ctl = os.path.join(self.mconfig.get("rabbitSbinPath", ""), "rabbitmqctl")
        if not (shutil.which(ctl) or os.path.exists(ctl)):
            return None
        try:
            out = subprocess.run(
                [ctl, "list_queues", "--quiet", "--no-table-headers", "name",
                 "messages_ram", "messages_persistent", "memory"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, timeout=30, check=True,
            ).stdout.decode()
        except Exception as e:
            self.alerts.add(f"Could not inspect queues via rabbit controller: {e}")
            return None
        rows = []
        for line in out.splitlines():
            parts = line.split()
            if len(parts) >= 4:
                rows.append((parts[0], int(parts[1]) + int(parts[2]), int(parts[3]) / 1024.0 / 1024.0))
        return rows

    def broker_is_running(self) -> Optional[bool]:  # pragma: no cover - live broker
        if self.runtime.config.get("brokerBackend") != "amqp":
            return None
        ctl = os.path.join(self.mconfig.get("rabbitSbinPath", ""), "rabbitmqctl")
        if not (shutil.which(ctl) or os.path.exists(ctl)):
            return None
        try:
            subprocess.run([ctl, "status"], stdout=subprocess.DEVNULL,
                           stderr=subprocess.DEVNULL, timeout=30, check=True)
            return True
        except Exception:
            return False

    def start_broker(self) -> None:  # pragma: no cover - live broker
        server = os.path.join(self.mconfig.get("rabbitSbinPath", ""), "rabbitmq-server")
        try:
            subprocess.run([server, "-detached"], timeout=60, check=True)
        except Exception as e:
            self.alerts.add(f"Could not start RabbitMQ: {e}")

    def inspect_all(self) -> None:
        running = self.broker_is_running()
        if running is False:
            self.alerts.add("RabbitMQ is down, attempting to restart it.")
            self.start_broker()
        self.inspect_disk_space()
        self.inspect_queues()
        self.inspect_modules()
        self.inspect_module_health()

    # -- log retention (apm_manager.js:532-571) -------------------------------
    def cleanup_logs(self) -> int:
        log_dir = self.runtime.config.get("logDir", "logs")
        days = float(self.mconfig.get("appLogRetentionDays", 7))
        cutoff = time.time() - days * 86400
        removed = 0
        try:
            names = os.listdir(log_dir)
        except OSError:
            return 0
        for name in names:
            path = os.path.join(log_dir, name)
            try:
                if os.path.isfile(path) and os.path.getmtime(path) < cutoff:
                    os.unlink(path)
                    removed += 1
            except OSError:
                continue
        if removed:
            self.runtime.logger.info(f"Removed {removed} logs older than {days} days")
        return removed

    # -- lifecycle ------------------------------------------------------------
    def shutdown(self, *, stop_children: Optional[bool] = None) -> None:
        self.alerts.stop()
        if self.recorder_store is not None:
            try:  # runtime timers are already stopping: seal the store
                self.recorder_store.close()
            except Exception:
                pass
        if stop_children is None:
            # Reference parity: controller.sh stop kills only the manager and
            # the next start reaps stale module PIDs (apm_manager.js:624).
            # Opt into full teardown with stopChildrenOnShutdown.
            stop_children = bool(self.mconfig.get("stopChildrenOnShutdown", False))
        if stop_children:
            for mod in self.modules:
                mod.stop()


def main(config_path: Optional[str] = None) -> None:
    from ..runtime.module_base import ModuleRuntime

    runtime = ModuleRuntime("applicationManager", config_path=config_path)
    ManagerApp(runtime)
    runtime.logger.info("APM manager started")
    runtime.run_forever()


if __name__ == "__main__":
    main()
