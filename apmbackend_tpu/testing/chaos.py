"""Transport fault injection (chaos) — the testing seam the reference lacked.

The reference's failure story was only ever exercised in production
(SURVEY.md §5.3: "No fault injection exists"); its manual-test seams were
consume toggles and destructive queue peeks. This module makes broker
misbehavior a first-class, DETERMINISTIC test input: wrap any
:class:`~apmbackend_tpu.transport.base.Channel` in a :class:`ChaosChannel`
and inject

- **forced-full windows** (``force_full()`` / ``release()``): ``send()``
  refuses like a broker under memory/disk alarm, driving the real
  pause → buffer → drain → resume stack (queue.js:245-263, 88-106 contract)
  on demand instead of by luck;
- **message drops** (``drop_p``): delivery loss after the ack — the
  at-most-once window the reference accepts (queue.js:277-283);
- **duplicate deliveries** (``dup_p``): broker redelivery, which
  ack-on-receipt consumers see as double-processing.

Randomness is a seeded ``random.Random``: a failing chaos test replays
bit-identically. Counters (:class:`ChaosStats`) expose exactly what was
injected so assertions can account for every message.

This is a *testing* module: production code never constructs it. Wire it by
wrapping the backend factory handed to ``QueueManager``::

    broker = MemoryBroker()
    chaos = ChaosChannel(MemoryChannel(broker), drop_p=0.1, seed=7)
    qm = QueueManager(lambda direction: chaos if direction == "p" else ...)

**Process-level harness** (the kill−9 tier): :class:`SpoolChannel` is a
durable file-backed broker whose consumer cursor only advances on ``ack()``
— at-least-once semantics that survive SIGKILL of the consumer process —
and :class:`ChaosWorkerHarness` spawns a REAL worker subprocess (the
production ``WorkerApp`` epoch cycle, ``deliveryMode: atLeastOnce``) over
such a spool, kills it −9 mid-stream, restarts it, and exposes the final
engine snapshot + delivery stats so tests can assert the recovered run is
EQUAL to a crash-free golden run. The chaos seams compose: the harness can
inject duplicate deliveries (``dup_p``) on top of the kill/restart cycle.
"""

from __future__ import annotations

import json
import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List

from ..transport.base import Channel


@dataclass
class ChaosStats:
    refused_sends: int = 0
    dropped: int = 0
    duplicated: int = 0
    delivered: int = 0
    sent: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def _bump(self, name: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + n)


class ChaosChannel(Channel):
    """Fault-injecting decorator around a real transport Channel."""

    def __init__(
        self,
        inner: Channel,
        *,
        drop_p: float = 0.0,
        dup_p: float = 0.0,
        seed: int = 0,
    ):
        if not (0.0 <= drop_p <= 1.0 and 0.0 <= dup_p <= 1.0):
            raise ValueError("drop_p/dup_p must be probabilities")
        self.inner = inner
        self.drop_p = drop_p
        self.dup_p = dup_p
        self.stats = ChaosStats()
        self._rng = random.Random(seed)
        self._forced_full = False
        self._drain_cbs: List[Callable[[], None]] = []
        # real backend drains propagate through the same callback list the
        # chaos-released drains use
        inner.on_drain(self._fire_drain)

    # -- producer-side faults -------------------------------------------------
    def force_full(self) -> None:
        """Subsequent ``send()`` calls refuse (broker alarm engaged)."""
        self._forced_full = True

    def release(self) -> None:
        """End the forced-full window and fire the drain event, exactly like
        a broker clearing its alarm (connection.unblocked -> drain)."""
        self._forced_full = False
        self._fire_drain()

    def send(self, name: str, payload: bytes, headers=None) -> bool:
        if self._forced_full:
            self.stats._bump("refused_sends")
            return False
        ok = self.inner.send(name, payload, headers)
        if ok:
            self.stats._bump("sent")
        return ok

    # -- consumer-side faults -------------------------------------------------
    def consume(self, name: str, callback: Callable[[bytes], None], consumer_tag: str,
                manual_ack: bool = False) -> None:
        from ..transport.base import accepts_headers

        if manual_ack:

            def chaotic(payload: bytes, headers=None, token=None) -> None:
                # manual-ack semantics under chaos: a DROP leaves the token
                # unacked on the broker ledger (delivery loss before
                # processing — redelivered on close/restart, nothing is
                # silently gone); a DUP replays the same payload+msg_id+token
                # (the consumer's dedup window must catch it; double-acking
                # one token is idempotent by the Channel contract)
                if self.drop_p and self._rng.random() < self.drop_p:
                    self.stats._bump("dropped")
                    return
                self.stats._bump("delivered")
                callback(payload, headers, token)
                if self.dup_p and self._rng.random() < self.dup_p:
                    self.stats._bump("duplicated")
                    self.stats._bump("delivered")
                    callback(payload, headers, token)

            self.inner.consume(name, chaotic, consumer_tag, manual_ack=True)
            return

        wants_headers = accepts_headers(callback)

        def chaotic(payload: bytes, headers=None) -> None:
            # the backend already removed the message (ack-on-receipt): a
            # drop here IS the at-most-once loss window
            deliver = (
                (lambda: callback(payload, headers)) if wants_headers
                else (lambda: callback(payload))
            )
            if self.drop_p and self._rng.random() < self.drop_p:
                self.stats._bump("dropped")
                return
            self.stats._bump("delivered")
            deliver()
            if self.dup_p and self._rng.random() < self.dup_p:
                self.stats._bump("duplicated")
                self.stats._bump("delivered")
                deliver()

        self.inner.consume(name, chaotic, consumer_tag)

    # -- passthrough ----------------------------------------------------------
    def ack(self, tokens) -> None:
        self.inner.ack(tokens)

    def assert_queue(self, name: str) -> None:
        self.inner.assert_queue(name)

    def cancel(self, consumer_tag: str) -> None:
        self.inner.cancel(consumer_tag)

    def on_drain(self, callback: Callable[[], None]) -> None:
        self._drain_cbs.append(callback)

    def close(self) -> None:
        self.inner.close()

    def _fire_drain(self) -> None:
        for cb in list(self._drain_cbs):
            cb()


# ---------------------------------------------------------------------------
# Process-level harness: durable spool broker + kill−9 worker driver
# ---------------------------------------------------------------------------


# SpoolChannel moved to transport/spool.py (it is a real transport backend,
# not a test double — the production worker runs over it in the chaos and
# hostile-storage tiers); re-exported here for compatibility.
from ..transport.spool import SpoolChannel, read_spool_cursor  # noqa: E402
from ..transport.spool import _SpoolQueue as _SpoolQueue  # noqa: E402 (re-export)


class ChaosWorkerHarness:
    """Drive a REAL worker subprocess through kill−9 chaos.

    The child is the production stack — ``WorkerApp`` with ``deliveryMode:
    atLeastOnce`` over a :class:`SpoolChannel` (optionally chaos-wrapped with
    duplicate injection) — launched via ``python -m
    apmbackend_tpu.testing.chaos --child``. The harness appends tx lines to
    the durable spool, watches the committed cursor, SIGKILLs / restarts the
    child at will, and collects the final engine snapshot + delivery stats.

    Crash-equivalence protocol (tests/test_chaos_harness.py): run one
    harness to completion with no kills (golden), another over the same line
    stream with kills + dup chaos, then compare the two final resume
    snapshots array-for-array.

    ``checkpoint_mode="delta"`` runs the child on the incremental delta
    chain (deltachain.py) under ``<workdir>/chain``; at clean exit the child
    exports a FULL snapshot to ``resume_path``, so the same array-for-array
    comparison covers delta runs — including cross-mode comparisons (a delta
    chaos run vs a full-snapshot golden run must still be bit-identical).
    ``fault_env`` injects hostile-storage faults (deltachain.StorageFaultPlan
    grammar) via ``APM_CHAOS_FS``: a string applies to every child
    generation, a ``{generation: spec}`` dict targets specific restarts
    (e.g. kill-during-compaction only in generation 1).
    """

    QUEUE = "transactions"

    def __init__(self, workdir: str, *, dup_p: float = 0.0, seed: int = 0,
                 capacity: int = 64, save_every_s: float = 0.4,
                 checkpoint_mode: str = "full", compact_every: int = 0,
                 fault_env=None, event_log: bool = False):
        import sys

        self.workdir = os.path.abspath(workdir)
        self.spool_dir = os.path.join(self.workdir, "spool")
        os.makedirs(self.spool_dir, exist_ok=True)
        self.resume_path = os.path.join(self.workdir, "engine.resume.npz")
        self.stats_path = os.path.join(self.workdir, "stats.json")
        self.done_path = os.path.join(self.workdir, "DONE")
        self.log_path = os.path.join(self.workdir, "child.log")
        self.dup_p = dup_p
        self.seed = seed
        self.capacity = capacity
        self.save_every_s = save_every_s
        self.checkpoint_mode = checkpoint_mode
        self.chain_dir = os.path.join(self.workdir, "chain")
        self.compact_every = compact_every
        self.fault_env = fault_env
        # crash flight-recorder bundles (obs/flight): the child journals
        # here on a fast cadence; a kill−9 leaves journal+sentinel behind
        # and the RESTARTED child promotes them into a ...-crash.json bundle
        self.flight_dir = os.path.join(self.workdir, "flight")
        # protocol event log (analysis/protocol conformance): the child
        # appends worker events; the harness appends crash/corrupt markers
        # at its injection points so the replay knows what was done to it
        self.event_log_path = (
            os.path.join(self.workdir, "events.jsonl") if event_log else None)
        self.python = sys.executable
        self.proc = None
        self.generation = 0
        self._seq = 0
        self._producer = SpoolChannel(self.spool_dir)

    def _mark_event(self, ev: str, **fields) -> None:
        if self.event_log_path is None:
            return
        fields["ev"] = ev
        fields["ts"] = time.time()
        with open(self.event_log_path, "a", encoding="utf-8") as fh:
            fh.write(json.dumps(fields, separators=(",", ":")) + "\n")

    def events(self) -> list:
        """The protocol event log so far (torn tail tolerated)."""
        from ..analysis.protocol.conformance import read_event_log

        assert self.event_log_path is not None, "harness built without event_log"
        return read_event_log(self.event_log_path)

    def flight_bundles(self) -> list:
        """(path, parsed body) for every flight bundle the child produced —
        parse errors raise (an unreadable bundle is the bug this asserts on)."""
        from ..obs.flight import list_bundles

        return list_bundles(self.flight_dir)

    def wait_rearmed(self, n_bundles: int, timeout_s: float = 60.0) -> None:
        """Block until the restarted child has promoted the previous
        generation's journal+sentinel shadow into crash bundle ``n_bundles``
        (boot-time ``recover_crash``) AND its OWN live journal carries the
        worker sources again (WorkerApp registered + a journal tick ran).

        The spool cursor can race far past the nominal kill points, so
        without this the next SIGKILL can land mid-boot — before the
        recorder re-arms (two crashes legitimately collapse into one
        promotion) or before the journal is source-populated. Crucially the
        journal must be the *current* generation's: ``recover_crash``
        consumes only the sentinel, so the dead generation's journal (which
        already had ``engine_health``) stays on disk until the new child's
        first tick overwrites it. The journal's ``pid`` stamp (obs/flight
        ``snapshot``) is matched against the live child to reject that
        stale read.
        """
        assert self.proc is not None and self.proc.poll() is None, \
            "wait_rearmed needs a live child (call start() first)"
        journal = os.path.join(self.flight_dir, "tpu_worker.journal.json")
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            promoted = sum(
                1 for _p, b in self.flight_bundles() if b.get("recovered")
            )
            if promoted >= n_bundles:
                try:
                    with open(journal, "r", encoding="utf-8") as fh:
                        body = json.load(fh)
                except Exception:
                    body = {}
                if ("engine_health" in body
                        and body.get("pid") == self.proc.pid):
                    return
            time.sleep(0.05)
        raise TimeoutError(
            f"crash bundle {n_bundles} / re-armed journal (pid "
            f"{self.proc.pid}) never appeared; see {self.log_path}"
        )

    # -- stream --------------------------------------------------------------
    def send_line(self, line: str) -> None:
        self._seq += 1
        self._producer.send(
            self.QUEUE, line.encode("utf-8"),
            {"ingest_ts": time.time(), "msg_id": f"h-{self._seq}"},
        )

    @property
    def sent(self) -> int:
        return self._seq

    # -- child lifecycle -----------------------------------------------------
    def start(self):
        import subprocess

        assert self.proc is None or self.proc.poll() is not None
        self.generation += 1
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("APM_CHAOS_FS", None)
        fault = self.fault_env
        if isinstance(fault, dict):
            fault = fault.get(self.generation)
        if fault:
            env["APM_CHAOS_FS"] = fault
        argv = [
            self.python, "-m", "apmbackend_tpu.testing.chaos", "--child",
            "--spool-dir", self.spool_dir,
            "--resume", self.resume_path,
            "--queue", self.QUEUE,
            "--stats-out", self.stats_path,
            "--done-file", self.done_path,
            "--capacity", str(self.capacity),
            "--save-every-s", str(self.save_every_s),
            "--dup-p", str(self.dup_p),
            "--seed", str(self.seed + self.generation),
            "--flight-dir", self.flight_dir,
            "--checkpoint-mode", self.checkpoint_mode,
            "--chain-dir", self.chain_dir,
            "--compact-every", str(self.compact_every),
        ]
        if self.event_log_path:
            argv += ["--event-log", self.event_log_path]
        log_fh = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            argv,
            stdout=log_fh, stderr=log_fh, stdin=subprocess.DEVNULL,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
            env=env,
        )
        log_fh.close()
        return self.proc

    def kill9(self) -> None:
        """SIGKILL: no atexit, no signal handler, no flush — the real thing."""
        import signal as _signal

        if self.proc is not None and self.proc.poll() is None:
            os.kill(self.proc.pid, _signal.SIGKILL)
            self.proc.wait(timeout=30)
            self._mark_event("crash", gen=self.generation)

    def wait_child_death(self, timeout_s: float = 120.0) -> int:
        """Block until the child dies on its own — the fault-plan SIGKILL
        scenarios (kill:compact=...) where the child, not the harness, picks
        the crash instant. Returns the (negative-signal) exit code."""
        rc = self.proc.wait(timeout=timeout_s)
        if rc != 0:
            self._mark_event("crash", gen=self.generation)
        return rc

    def acked(self) -> int:
        return read_spool_cursor(self.spool_dir, self.QUEUE)

    def chain_tail_segment(self):
        """Path of the newest delta segment on disk (None when no deltas)."""
        segs = sorted(
            n for n in os.listdir(self.chain_dir)
            if n.startswith("delta-") and n.endswith(".seg")
        )
        return os.path.join(self.chain_dir, segs[-1]) if segs else None

    def corrupt_chain_tail(self, mode: str) -> str:
        """Damage the chain tail between child generations — the hostile-
        storage matrix rows a SIGKILL alone cannot produce on a journaling
        filesystem: ``truncate`` (torn final write: half the segment),
        ``garbage`` (bit rot in the payload), ``header`` (truncated inside
        the header framing), ``stale-dup`` (a leftover same-name future
        segment from a dead incarnation: the tail copied to epoch+1, which
        recovery must reject via the uid linkage, never replay)."""
        seg = self.chain_tail_segment()
        assert seg is not None, "no delta segment to corrupt"
        blob = open(seg, "rb").read()
        if mode == "truncate":
            # apm: allow(durability-discipline): deliberately torn bytes — this IS the hostile-storage injector
            open(seg, "wb").write(blob[: max(1, len(blob) // 2)])
        elif mode == "header":
            # apm: allow(durability-discipline): deliberately torn header framing — hostile-storage injector
            open(seg, "wb").write(blob[: len(b"APMDCSG1") + 5])
        elif mode == "garbage":
            mid = len(blob) // 2  # 0xA5: never a no-op over real segment bytes
            # apm: allow(durability-discipline): deliberate bit rot — hostile-storage injector
            open(seg, "wb").write(blob[:mid] + b"\xa5" * 16 + blob[mid + 16:])
        elif mode == "stale-dup":
            epoch = int(os.path.basename(seg)[6:-4])
            dup = os.path.join(self.chain_dir, f"delta-{epoch + 1:012d}.seg")
            open(dup, "wb").write(blob)
            self._mark_event("corrupt", mode=mode)
            return dup
        else:
            raise ValueError(f"unknown corruption mode {mode!r}")
        self._mark_event("corrupt", mode=mode)
        return seg

    def wait_acked(self, n: int, timeout_s: float = 120.0) -> int:
        """Block until the committed cursor reaches ``n`` (or timeout); the
        kill-point selector for mid-stream SIGKILLs."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            got = self.acked()
            if got >= n:
                return got
            if self.proc is not None and self.proc.poll() is not None:
                raise RuntimeError(
                    f"chaos child exited rc={self.proc.returncode} before acking {n} "
                    f"(got {got}); see {self.log_path}"
                )
            time.sleep(0.02)
        raise TimeoutError(f"cursor stuck at {self.acked()} < {n}; see {self.log_path}")

    def finish(self, timeout_s: float = 180.0) -> dict:
        """Signal end-of-stream, wait for the child's final epoch commit and
        graceful exit, and return its stats JSON."""
        tmp = self.done_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump({"total": self._seq}, fh)
        os.replace(tmp, self.done_path)
        rc = self.proc.wait(timeout=timeout_s)
        if rc != 0:
            raise RuntimeError(f"chaos child exit rc={rc}; see {self.log_path}")
        with open(self.stats_path, "r", encoding="utf-8") as fh:
            return json.load(fh)

    def close(self) -> None:
        self.kill9()
        self._producer.close()


class QueryLoad:
    """Concurrent dashboard-load generator against query-plane URLs —
    the read-side chaos instrument (ISSUE 20). Seeded like every chaos
    seam: thread ``i`` walks its own ``Random(seed + i)`` URL sequence,
    so a failing drill replays the same request mix. Collects status
    codes, transport errors, and latencies; ``stop()`` returns the
    summary the kill−9 drill asserts on (zero 5xx, p95 bound).

    Degraded-serving honesty is the point: an HTTP error status is
    recorded under its code (a 5xx mid-drill is a FINDING), while a
    transport-level failure (connection refused while the front door
    itself restarts) counts separately as an error, not a 5xx.
    """

    def __init__(self, urls: List[str], *, threads: int = 4,
                 timeout_s: float = 5.0, seed: int = 0):
        if not urls:
            raise ValueError("QueryLoad needs at least one URL")
        self.urls = list(urls)
        self.threads = max(1, int(threads))
        self.timeout_s = float(timeout_s)
        self.seed = int(seed)
        self._stop = threading.Event()
        self._workers: List[threading.Thread] = []
        self._lock = threading.Lock()
        self._codes: dict = {}  # guarded-by: _lock
        self._latencies_ms: List[float] = []  # guarded-by: _lock
        self._errors = 0  # guarded-by: _lock

    def _one(self, rng: random.Random) -> None:
        import urllib.error
        import urllib.request

        url = rng.choice(self.urls)
        t0 = time.monotonic()
        try:
            with urllib.request.urlopen(url, timeout=self.timeout_s) as resp:
                resp.read()
                code = resp.status
        except urllib.error.HTTPError as e:
            code = e.code
        except Exception:
            with self._lock:
                self._errors += 1
            return
        ms = (time.monotonic() - t0) * 1000.0
        with self._lock:
            self._codes[code] = self._codes.get(code, 0) + 1
            self._latencies_ms.append(ms)

    def start(self) -> "QueryLoad":
        def run(i):
            rng = random.Random(self.seed + i)
            while not self._stop.is_set():
                self._one(rng)

        self._workers = [
            threading.Thread(target=run, args=(i,), daemon=True,
                             name=f"query-load-{i}")
            for i in range(self.threads)
        ]
        for t in self._workers:
            t.start()
        return self

    def stop(self) -> dict:
        self._stop.set()
        for t in self._workers:
            t.join(timeout=self.timeout_s + 1.0)
        with self._lock:
            lats = sorted(self._latencies_ms)
            codes = dict(self._codes)
            errors = self._errors

        def pct(p):
            if not lats:
                return None
            return lats[min(len(lats) - 1, int(p * (len(lats) - 1)))]

        return {
            "requests": sum(codes.values()),
            "codes": codes,
            "five_xx": sum(n for c, n in codes.items() if 500 <= c < 600),
            "errors": errors,
            "p50_ms": pct(0.50),
            "p95_ms": pct(0.95),
        }


def _child_main(argv=None) -> int:
    """The harness child: the production worker epoch cycle over a spool.

    Everything between SpoolChannel and the engine snapshot is the REAL
    production code path — WorkerApp's at-least-once consume/dedup/epoch
    logic, PipelineDriver's checkpoint — not a test double. The only
    harness-specific parts are the spool transport and the DONE/stats
    files."""
    import argparse

    ap = argparse.ArgumentParser(prog="apmbackend_tpu.testing.chaos --child")
    ap.add_argument("--spool-dir", required=True)
    ap.add_argument("--resume", required=True)
    ap.add_argument("--queue", default="transactions")
    ap.add_argument("--stats-out", required=True)
    ap.add_argument("--done-file", required=True)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--save-every-s", type=float, default=0.4)
    ap.add_argument("--dup-p", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--flight-dir", default=None)
    ap.add_argument("--checkpoint-mode", default="full", choices=("full", "delta"))
    ap.add_argument("--chain-dir", default=None)
    ap.add_argument("--compact-every", type=int, default=0)
    ap.add_argument("--event-log", default=None)
    args = ap.parse_args(argv)

    from ..config import default_config
    from ..runtime.module_base import ModuleRuntime
    from ..runtime.worker import WorkerApp
    from ..transport.base import QueueManager

    cfg = default_config()
    eng = cfg["tpuEngine"]
    eng["serviceCapacity"] = args.capacity
    eng["samplesPerBucket"] = 64
    eng["deliveryMode"] = "atLeastOnce"
    eng["metricsPort"] = None
    if args.checkpoint_mode == "delta":
        # delta-chain epoch commits; the full `--resume` npz is written only
        # as a clean-exit EXPORT so the harness's array-for-array comparison
        # (and cross-mode full-vs-delta comparisons) keep working
        eng["checkpointMode"] = "delta"
        eng["checkpointChainDir"] = args.chain_dir
        eng["resumeFileFullPath"] = None
        eng["checkpointCompactEveryEpochs"] = args.compact_every
        # fast retry cadence: the ENOSPC scenarios must clear inside a test
        eng["checkpointWriteRetryBaseSeconds"] = 0.05
        eng["checkpointWriteRetryMaxSeconds"] = 0.5
    else:
        eng["resumeFileFullPath"] = args.resume
    if args.event_log:
        # protocol event log for the trace-conformance tier: the REAL
        # worker's deliver/feed/checkpoint/ack stream, replayed against
        # the models by tests/test_protocol_conformance.py
        eng["protocolEventLog"] = args.event_log
    cfg["streamCalcZScore"]["defaults"] = [{"LAG": 6, "THRESHOLD": 3.0, "INFLUENCE": 0.1}]
    cfg["streamCalcStats"]["inQueue"] = args.queue
    # the resume-save timer IS the epoch cadence: short, so SIGKILLs land at
    # arbitrary points relative to commits
    cfg["streamCalcStats"]["resumeFileSaveFrequencyInSeconds"] = args.save_every_s
    cfg["streamProcessAlerts"]["alertsResumeFileFullPath"] = None
    cfg["logDir"] = None
    if args.flight_dir:
        # crash flight recorder under kill−9: journal on a sub-second
        # cadence so a SIGKILL at any instant leaves a fresh shadow; the
        # restarted child promotes it to a crash bundle at boot. The
        # recorder only READS pipeline state and writes under its own
        # directory — the bit-identical golden comparison is untouched.
        cfg["observability"]["flightDir"] = args.flight_dir
        cfg["observability"]["flightJournalSeconds"] = 0.2

    runtime = ModuleRuntime(
        "tpuEngine", config=cfg, install_signals=True, console_log=True
    )
    spools = {}

    def factory(direction: str):
        ch = SpoolChannel(args.spool_dir)
        spools[direction] = ch
        if direction == "c" and args.dup_p > 0:
            return ChaosChannel(ch, dup_p=args.dup_p, seed=args.seed)
        return ch

    runtime.qm = QueueManager(factory, 3600, logger=runtime.logger)
    worker = WorkerApp(runtime)
    consumer = spools["c"]
    consumer.start_pump_thread()

    total = None
    while True:
        if total is None and os.path.exists(args.done_file):
            try:
                with open(args.done_file, "r", encoding="utf-8") as fh:
                    total = int(json.load(fh)["total"])
            except Exception:
                total = None
        if total is not None and consumer.delivered_count(args.queue) >= total:
            # stream fully delivered: force the final epoch commit and stop
            # once every record is acked (committed)
            worker.save_state()
            if consumer.acked_count(args.queue) >= total:
                break
        time.sleep(0.02)

    consumer.stop()
    worker.shutdown()  # final save_state + ack inside
    if args.checkpoint_mode == "delta":
        # clean-exit export: the comparison snapshot (NOT a checkpoint — the
        # chain is the durable state; this npz exists for the harness's
        # bit-identical assertions against full-mode/golden runs)
        with worker._driver_lock:
            worker.driver.save_resume(args.resume)
    stats = {
        "epoch": worker._delivery_epoch,
        "deduped_total": worker._deduped_total,
        "unacked": len(worker._epoch_tokens),
        "acked": consumer.acked_count(args.queue),
        "services": worker.driver.registry.count,
        "latest_label": worker.driver._latest_label,
        "checkpoint_mode": args.checkpoint_mode,
        "checkpoint_write_failures": worker._ckpt_failures_total,
        "chain_epoch": (
            worker._ckpt_chain.tail_epoch if worker._ckpt_chain is not None else None
        ),
        "compactions": (
            worker._ckpt_chain.compactions if worker._ckpt_chain is not None else 0
        ),
    }
    tmp = args.stats_out + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(stats, fh)
    os.replace(tmp, args.stats_out)
    runtime.stop_timers()
    return 0


if __name__ == "__main__":
    import sys

    if "--child" in sys.argv:
        sys.argv.remove("--child")
        sys.exit(_child_main(sys.argv[1:]))
    raise SystemExit("usage: python -m apmbackend_tpu.testing.chaos --child ...")
