"""Pod-scale sharded serving spine (DESIGN.md §10).

The missing production topology between "one worker, 6.5× the per-chip
target" and "a fleet serving 1M+ metrics/s": service-hash partitioning at
the TRANSPORT layer, N shard workers each running the full production
epoch cycle (feed → tick → delta-chain checkpoint → ack) against its own
partition queue / dedup window / chain dir, and the quiesced rebalance
handoff implemented exactly as pre-verified by the protocol model checker
(analysis/protocol/shardmodel.py — PR 8 verified the protocol before this
module existed; keep the two in sync per the README "verifying a protocol
change" workflow).

Pieces:

- :func:`service_partition` — stable FNV-1a routing hash. Salted Python
  ``hash()`` would re-route the fleet on every restart; this one is pinned
  by tests to exact values so producers, shards, and rebalanced owners all
  agree across processes and releases.
- :class:`FleetPartitioner` — the producer side: one ProducerQueue per
  partition channel (``<base>.p<K>``), routing each tx line by its service
  (or server) key and stamping the partition id into the message headers
  (transport/base.py write_line), so consuming shards can verify routing
  discipline (the ``partition_header_mismatch`` model mutant shows what an
  unverified mismatch costs).
- :func:`write_handoff` / :func:`read_handoff` — the rebalance record:
  a partition's state rows (PipelineDriver.export_service_rows) + its
  dedup-window ids + the exporter's chain manifest, atomically written.
- :class:`FleetHarness` — launch/drive N REAL worker shards as
  subprocesses over a shared durable spool (the single-host deployment
  shape; the manager's ``shards`` moduleSetting is the supervised form).
  Supports kill−9 per shard, live-traffic rebalance via a control-file
  protocol, merged protocol-event logs for the fleet conformance checker,
  and per-shard state export for bit-identity assertions.

This mirrors the stream-to-compute-node scale-out of arxiv 2403.14352 and
the partitioned-stage pipeline framing of arxiv 1712.08285 (PAPERS.md).
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

# ---------------------------------------------------------------------------
# Partitioning
# ---------------------------------------------------------------------------

_FNV_OFFSET = 0x811C9DC5
_FNV_PRIME = 0x01000193


def service_partition(key: str, n_partitions: int) -> int:
    """Stable partition of a routing key: 32-bit FNV-1a over the UTF-8
    bytes, mod the partition count. Deterministic across processes,
    restarts, and machines (NEVER Python ``hash()`` — PYTHONHASHSEED would
    re-route the fleet per boot and orphan every dedup window)."""
    h = _FNV_OFFSET
    for b in key.encode("utf-8"):
        h = ((h ^ b) * _FNV_PRIME) & 0xFFFFFFFF
    return h % n_partitions


def resolve_partitions(shards: int, partitions: int = 0) -> int:
    """The effective partition count of a fleet: ``fleet.partitions`` when
    set, else 4 partitions per shard (P > N is the point — a rebalance
    moves a fine grain, not half a shard's keyspace). P < N would strand
    workers with nothing to own, so it is a config error."""
    if shards <= 0:
        raise ValueError("resolve_partitions needs shards > 0")
    if partitions in (0, None):
        return shards * 4
    p = int(partitions)
    if p < shards:
        raise ValueError(
            f"fleet.partitions={p} < fleet.shards={shards}: every shard "
            f"needs at least one partition to own")
    return p


def partition_queue(base: str, p: int) -> str:
    """The transport channel of partition ``p`` (``transactions.p3``)."""
    return f"{base}.p{p}"


def parse_partition(queue_name: str, base: str) -> Optional[int]:
    """Inverse of :func:`partition_queue`; None for foreign queue names."""
    prefix = f"{base}.p"
    if not queue_name.startswith(prefix):
        return None
    tail = queue_name[len(prefix):]
    return int(tail) if tail.isdigit() else None


def tx_partition_key(line: str, key: str = "service") -> Optional[str]:
    """The routing key of one wire line: tx lines partition by service
    (field 2) or server (field 1); non-tx lines return None (the caller
    routes them to partition 0 — they are rejected at the worker anyway,
    but deterministically, on one shard)."""
    p = line.split("|", 3)
    if len(p) < 4 or p[0] != "tx":
        return None
    return p[1] if key == "server" else p[2]


# ---------------------------------------------------------------------------
# Owner map: the seq-versioned partition -> shard read API (ISSUE 20)
# ---------------------------------------------------------------------------


class OwnerMap:
    """Seq-versioned view of partition → owner for read-side routing.

    The fleet query plane routes single-service reads by
    ``service_partition`` + this map, and needs rebalance consistency: a
    query racing a partition handoff must notice the move and retry
    rather than double-count or drop the moving partition. The contract
    is therefore *read-with-a-seq*: :meth:`read` returns ``(seq,
    owners)`` atomically, and the seq bumps ONLY when ownership actually
    changed — a reader that sees the same seq before and after its
    fan-out knows no partition moved underneath it.

    Owner values are routing-target names (opaque strings — the
    manager uses module names, the harness ``shard<k>``); feeds that
    observe integer shard ids convert before :meth:`update`. Partitions
    absent from the map have no known owner (their shard is dead or not
    yet scraped) and the reader falls back to scatter.

    Thread-safe: updated from scrape/rebalance paths, read from HTTP
    handler threads.
    """

    def __init__(self, owners: Optional[Dict[int, str]] = None):
        self._lock = threading.Lock()
        self._seq = 0  # guarded-by: _lock
        self._owners: Dict[int, str] = {}  # guarded-by: _lock
        if owners:
            self.update(owners)

    def update(self, owners: Dict[int, str]) -> int:
        """Replace the whole map; bumps the seq only on real change (a
        steady-state rescrape that observes the same ownership must not
        force query retries). Returns the current seq."""
        new = {int(p): o for p, o in dict(owners).items()}
        with self._lock:
            if new != self._owners:
                self._owners = new
                self._seq += 1
            return self._seq

    def move(self, partition: int, owner: str) -> int:
        """Record one executed handoff (the controller's post-adopt
        bookkeeping); bumps the seq only if the owner changed."""
        with self._lock:
            if self._owners.get(int(partition)) != owner:
                self._owners[int(partition)] = owner
                self._seq += 1
            return self._seq

    def read(self) -> Tuple[int, Dict[int, str]]:
        """``(seq, owners copy)`` — one atomic view+version."""
        with self._lock:
            return self._seq, dict(self._owners)


_OWNER_LINE_RE = re.compile(
    r'^apm_fleet_partition_owner\{[^}]*partition="(\d+)"[^}]*\}\s+'
    r'([0-9eE+.\-]+)', re.M)


def owner_map_from_fleet_text(text: str) -> Dict[int, int]:
    """Parse ``apm_fleet_partition_owner{partition="K"} <shard>`` lines out
    of a manager ``/fleet`` exposition -> {partition: shard id}. The
    standalone query plane bootstraps its owner feed from this (the
    manager synthesizes those lines from each shard's
    ``apm_partition_lag`` attribution)."""
    out: Dict[int, int] = {}
    for m in _OWNER_LINE_RE.finditer(text or ""):
        out[int(m.group(1))] = int(float(m.group(2)))
    return out


class FleetPartitioner:
    """Producer-side service-hash partitioner: shards the ``base`` queue
    into one ProducerQueue per partition channel. Every line routes by its
    stable key hash; headers carry ``partition`` (stamped by write_line)
    beside ``msg_id``/``ingest_ts``, so the at-least-once consumers keep
    their dedup semantics per partition and can verify routing."""

    def __init__(self, qm, base: str, n_partitions: int, *,
                 key: str = "service"):
        if n_partitions <= 0:
            raise ValueError("n_partitions must be positive")
        if key not in ("service", "server"):
            raise ValueError(f"fleet.partitionKey must be service|server, got {key!r}")
        self.base = base
        self.n = n_partitions
        self.key = key
        self.queues = []
        for p in range(n_partitions):
            q = qm.get_queue(partition_queue(base, p), "p")
            q.partition = p
            self.queues.append(q)

    def partition_of(self, line: str) -> int:
        k = tx_partition_key(line, self.key)
        return service_partition(k, self.n) if k is not None else 0

    def write_line(self, line: str, verbose: bool = False) -> int:
        """Route one wire line; returns the partition it went to."""
        p = self.partition_of(line)
        self.queues[p].write_line(line, verbose)
        return p

    def write_frames(self, blob: bytes, verbose: bool = False) -> Dict[int, int]:
        """Route one packed APF1 batch (transport/frames.py): split it by
        each record's stable key hash — read straight off the frame spans,
        no line decode — and send ONE sub-batch per partition, stamped
        with that partition's header like write_line routing. Returns
        {partition: records sent}. The split hash and the per-line
        write_line hash are the same FNV-1a over the same key bytes, so a
        frame-mode producer and a line-mode producer route every record
        identically (asserted by tests/test_frames.py)."""
        from ..transport import frames as _frames

        parts = _frames.split_by_partition(blob, self.n, key=self.key)
        out: Dict[int, int] = {}
        for p, sub in sorted(parts.items()):
            n = _frames.frame_count(sub)
            self.queues[p].write_frames(sub, n, verbose)
            out[p] = n
        return out

    def write_lines_frames(self, lines, verbose: bool = False) -> Dict[int, int]:
        """Frame-mode bulk send: group ``lines`` by partition and emit one
        packed batch per partition — the producer-side fan-out that turns
        N per-line sends into at most ``n_partitions`` transport messages.
        Returns {partition: records sent}."""
        from ..transport import frames as _frames

        groups: Dict[int, List[str]] = {}
        for line in lines:
            k = tx_partition_key(line, self.key)
            p = service_partition(k, self.n) if k is not None else 0
            groups.setdefault(p, []).append(line)
        out: Dict[int, int] = {}
        for p, grp in sorted(groups.items()):
            self.queues[p].write_frames(_frames.encode_lines(grp), len(grp), verbose)
            out[p] = len(grp)
        return out


# ---------------------------------------------------------------------------
# Handoff records
# ---------------------------------------------------------------------------


def write_handoff(path: str, data: dict, meta: dict) -> None:
    """Atomically write one rebalance handoff record: the partition's state
    rows (npz schema from export_service_rows) + a JSON meta block (window
    ids, epoch, chain manifest). tmp + rename like every durable write in
    this codebase — a crash mid-write must leave no half-record a retry
    could half-adopt."""
    import tempfile

    d = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(d, exist_ok=True)
    arrays = dict(data)
    arrays["handoff_meta"] = np.array(
        json.dumps(meta, separators=(",", ":")), dtype=object
    )
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as fh:
            np.savez_compressed(fh, **arrays)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def read_handoff(path: str) -> Tuple[dict, dict]:
    """Load a handoff record -> (row data dict, meta dict). Raises on a
    torn/unreadable file: the controller must retry the release, never
    adopt half a partition."""
    with np.load(path, allow_pickle=True) as npz:
        data = {name: npz[name] for name in npz.files}
    meta = json.loads(data.pop("handoff_meta").item())
    return data, meta


# ---------------------------------------------------------------------------
# Fleet harness: N real worker shards over a shared durable spool
# ---------------------------------------------------------------------------


class FleetShardProc:
    """One shard subprocess: the production WorkerApp in fleet mode over
    the shared spool, plus the control-file seam the harness drives
    rebalances through (a durable request/ack protocol that survives
    kill−9 on either side, unlike an HTTP call into a dying process)."""

    def __init__(self, harness: "FleetHarness", shard_id: int):
        self.h = harness
        self.shard_id = shard_id
        self.proc = None
        self.generation = 0
        self._ctl_seq = 0
        self.ctl_path = os.path.join(harness.workdir, f"shard{shard_id}.ctl.json")
        self.ctl_done_path = self.ctl_path + ".done"
        self.log_path = os.path.join(harness.workdir, f"shard{shard_id}.log")
        self.stats_path = os.path.join(harness.workdir, f"shard{shard_id}.stats.json")
        # exporter-port discovery (metrics=True): the shard asks for an
        # ephemeral port and writes the bound one here (ModuleRuntime's
        # APM_METRICS_PORT_FILE seam) so the harness/recorder can scrape it
        self.port_path = os.path.join(harness.workdir, f"shard{shard_id}.port")
        self.resume_path = os.path.join(
            harness.workdir, f"shard{shard_id}.engine.npz"
        )
        self.event_log_path = (
            os.path.join(harness.workdir, f"events-shard{shard_id}.jsonl")
            if harness.event_log else None
        )

    def start(self):
        import subprocess
        import sys

        assert self.proc is None or self.proc.poll() is not None
        self.generation += 1
        h = self.h
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   APM_SHARD_ID=str(self.shard_id))
        if h.metrics:
            env["APM_METRICS_PORT_FILE"] = self.port_path
            try:  # a stale port file must not alias a dead incarnation
                os.unlink(self.port_path)
            except OSError:
                pass
        argv = [
            sys.executable, "-m", "apmbackend_tpu.parallel.fleet", "--shard",
            "--workdir", h.workdir,
            "--shard-id", str(self.shard_id),
            "--shards", str(h.shards),
            "--partitions", str(h.partitions),
            "--capacity", str(h.capacity),
            "--samples-per-bucket", str(h.samples_per_bucket),
            "--save-every-s", str(h.save_every_s),
            "--feed-delay-s", str(h.feed_delay_s),
            "--checkpoint-mode", h.checkpoint_mode,
            "--compact-every", str(h.compact_every),
            "--partition-key", h.partition_key,
            "--lags", h.lags,
            "--queue", h.base_queue,
        ]
        if self.event_log_path:
            argv.append("--event-log")
        if h.metrics:
            argv.append("--metrics")
        if h.fast_alerts:
            argv.append("--fast-alerts")
        log_fh = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            argv, stdout=log_fh, stderr=log_fh, stdin=subprocess.DEVNULL,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
            env=env,
        )
        log_fh.close()
        return self.proc

    def kill9(self) -> None:
        import signal

        if self.proc is not None and self.proc.poll() is None:
            os.kill(self.proc.pid, signal.SIGKILL)
            self.proc.wait(timeout=30)
            self.h._mark_event("crash", shard=self.shard_id, gen=self.generation)

    def request(self, cmd: str, **fields) -> int:
        """Durably write one control request (tmp+rename, seq-numbered)
        WITHOUT waiting — the request outlives both sides of the channel:
        a restarted child finds a pending seq above its done-file and
        re-executes it, and a restarted controller can re-await the same
        seq. Returns the request's seq."""
        self._ctl_seq += 1
        req = dict(fields, cmd=cmd, seq=self._ctl_seq)
        tmp = self.ctl_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(req, fh)
        os.replace(tmp, self.ctl_path)
        return self._ctl_seq

    def wait_done(self, seq: int, timeout_s: float = 120.0, *,
                  cmd: str = "?", die_on_death: bool = True) -> dict:
        """Block for the child's durable ack of request ``seq``. Raises on
        child-reported failure (with its error string), child death (when
        ``die_on_death`` — the rebalance controller passes False so it can
        restart the child and re-await the SAME seq), or timeout."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                with open(self.ctl_done_path, "r", encoding="utf-8") as fh:
                    done = json.load(fh)
            except (OSError, ValueError):
                done = None
            if done and int(done.get("seq", -1)) == seq:
                if not done.get("ok"):
                    raise RuntimeError(
                        f"shard {self.shard_id} {cmd} failed: {done.get('error')}"
                    )
                return done.get("result") or {}
            if die_on_death and self.proc is not None \
                    and self.proc.poll() is not None:
                raise RuntimeError(
                    f"shard {self.shard_id} died (rc={self.proc.returncode}) "
                    f"during {cmd}; see {self.log_path}"
                )
            time.sleep(0.02)
        raise TimeoutError(f"shard {self.shard_id} {cmd} timed out")

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def control(self, cmd: str, timeout_s: float = 120.0, **fields) -> dict:
        """Write one control request and block for the child's durable ack.
        Raises on child-reported failure (with its error string) or child
        death — the caller decides whether to retry."""
        seq = self.request(cmd, **fields)
        return self.wait_done(seq, timeout_s, cmd=cmd)

    def stats(self) -> dict:
        with open(self.stats_path, "r", encoding="utf-8") as fh:
            return json.load(fh)


class FleetHarness:
    """Drive the whole sharded spine on one host: a partitioning producer,
    N real shard subprocesses over one durable spool directory, rebalance
    control, and merged observability for assertions and the fleet bench."""

    def __init__(self, workdir: str, *, shards: int = 4, partitions: int = 0,
                 capacity: int = 64,
                 samples_per_bucket: int = 64, save_every_s: float = 0.4,
                 feed_delay_s: float = 0.05, checkpoint_mode: str = "delta",
                 compact_every: int = 0, partition_key: str = "service",
                 lags: str = "6", base_queue: str = "transactions",
                 event_log: bool = False, metrics: bool = False,
                 fast_alerts: bool = False):
        from ..transport.base import QueueManager
        from ..transport.spool import SpoolChannel

        self.workdir = os.path.abspath(workdir)
        self.spool_dir = os.path.join(self.workdir, "spool")
        os.makedirs(self.spool_dir, exist_ok=True)
        self.shards = shards
        self.partitions = resolve_partitions(shards, partitions)
        self.capacity = capacity
        self.samples_per_bucket = samples_per_bucket
        self.save_every_s = save_every_s
        self.feed_delay_s = feed_delay_s
        self.checkpoint_mode = checkpoint_mode
        self.compact_every = compact_every
        self.partition_key = partition_key
        self.lags = lags
        self.base_queue = base_queue
        self.event_log = event_log
        self.metrics = metrics
        self.fast_alerts = fast_alerts
        self.done_path = os.path.join(self.workdir, "DONE.json")
        self._producer_channel = SpoolChannel(self.spool_dir)
        self._qm = QueueManager(lambda _d: self._producer_channel, 3600)
        self.partitioner = FleetPartitioner(
            self._qm, base_queue, self.partitions, key=partition_key
        )
        self.procs: Dict[int, FleetShardProc] = {
            k: FleetShardProc(self, k) for k in range(shards)
        }
        # last port each shard ever published: lets the recorder targets
        # feed keep scraping (and counting failures against) a dead or
        # restarting shard whose port file is currently absent
        self._last_ports: Dict[int, int] = {}
        # shards the targets feed already paid its one startup wait for —
        # afterwards the feed only polls, so a shard that never publishes
        # cannot stall every scrape pass
        self._port_waited: set = set()
        self.sent_per_queue: Dict[str, int] = {
            partition_queue(base_queue, p): 0 for p in range(self.partitions)
        }
        # seq-versioned routing view for the query plane: seeded with the
        # static modulo placement the shards boot with, advanced by
        # rebalance() as handoffs execute
        self.owner_map = OwnerMap(
            {p: f"shard{p % shards}" for p in range(self.partitions)}
        )

    # -- stream --------------------------------------------------------------
    def send_line(self, line: str) -> int:
        p = self.partitioner.write_line(line)
        self.sent_per_queue[partition_queue(self.base_queue, p)] += 1
        return p

    def send_lines(self, lines) -> Dict[int, int]:
        """Frame-mode bulk send: route ``lines`` as at most one packed
        APF1 batch per partition. ``sent_per_queue`` counts spool RECORDS
        (one per batch written), because that is the unit the drain/ack
        accounting compares against: shard exit waits on per-queue
        ``delivered_count``/``acked_count`` and ``acked()`` reads the
        spool cursor, all of which advance once per spool record whether
        it carries one line or a thousand. Returns {partition: records}."""
        routed = self.partitioner.write_lines_frames(lines)
        for p in routed:
            self.sent_per_queue[partition_queue(self.base_queue, p)] += 1
        return routed

    def start_all(self) -> None:
        for proc in self.procs.values():
            proc.start()

    def start(self, k: int) -> None:
        self.procs[k].start()

    def kill9(self, k: int) -> None:
        self.procs[k].kill9()

    # -- telemetry plumbing (metrics=True) -----------------------------------
    def metrics_port(self, k: int, timeout_s: float = 15.0) -> int:
        """Bound exporter port of shard ``k`` (ephemeral ports: the shard
        writes it via the APM_METRICS_PORT_FILE seam once the exporter is
        up). Always tries at least one read (``timeout_s=0`` = poll once);
        raises TimeoutError if the shard never publishes one in time."""
        path = self.procs[k].port_path
        deadline = time.time() + timeout_s
        while True:
            try:
                with open(path, "r", encoding="utf-8") as fh:
                    port = int(fh.read().strip())
                self._last_ports[k] = port
                return port
            except (OSError, ValueError):
                if time.time() >= deadline:
                    raise TimeoutError(
                        f"shard {k} never published its metrics port ({path})")
                time.sleep(0.05)

    def metrics_url(self, k: int, timeout_s: float = 15.0) -> str:
        return f"http://127.0.0.1:{self.metrics_port(k, timeout_s)}"

    def metrics_targets(self, timeout_s: float = 15.0):
        """``[(name, base_url)]`` for every shard — the FleetRecorder's
        targets feed. Never raises and never stalls steady-state scrape
        passes: ``timeout_s`` bounds ONE startup wait per shard that has
        not published a port yet; afterwards the feed only polls. A shard
        whose port file is absent (kill −9, or mid-restart after start()
        unlinked it) reuses its last known port — the recorder counts the
        failed scrape and moves on — and a shard with no known port yet
        is skipped for this pass instead of failing the whole feed."""
        out = []
        for k in sorted(self.procs):
            if k in self._last_ports or k in self._port_waited:
                wait = 0.0
            else:
                wait = timeout_s
                self._port_waited.add(k)
            try:
                port = self.metrics_port(k, wait)
            except TimeoutError:
                port = self._last_ports.get(k)
                if port is None:
                    continue
            out.append((f"shard{k}", f"http://127.0.0.1:{port}"))
        return out

    # -- rebalance (the two-phase controller, shardmodel semantics) ----------
    def rebalance(self, p: int, frm: int, to: int,
                  timeout_s: float = 120.0) -> dict:
        """Move partition ``p`` from shard ``frm`` to ``to`` under live
        traffic. The release returns only after the releasing shard's
        commit landed (quiesce + export + drop are durable); only then is
        the record handed to the adopter — the two commits bracket the
        window in which the partition's rows exist solely in the handoff
        file, and nobody consumes its queue during that window."""
        handoff = os.path.join(self.workdir, f"handoff-p{p}-s{frm}-s{to}.npz")
        released = self.procs[frm].control(
            "release", partition=p, path=handoff, timeout_s=timeout_s
        )
        adopted = self.procs[to].control(
            "adopt", partition=p, path=handoff, timeout_s=timeout_s
        )
        self._mark_event("rebalance", partition=p, frm=frm, to=to)
        self.owner_map.move(p, f"shard{to}")
        return {"released": released, "adopted": adopted, "path": handoff}

    # -- completion ----------------------------------------------------------
    def finish(self, timeout_s: float = 300.0) -> Dict[int, dict]:
        """Publish end-of-stream totals, wait for every live shard to drain
        + ack its owned queues and exit cleanly; returns per-shard stats."""
        tmp = self.done_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump({"totals": self.sent_per_queue}, fh)
        os.replace(tmp, self.done_path)
        out = {}
        deadline = time.monotonic() + timeout_s
        for k, proc in self.procs.items():
            if proc.proc is None:
                continue
            rc = proc.proc.wait(timeout=max(1.0, deadline - time.monotonic()))
            if rc != 0:
                raise RuntimeError(
                    f"shard {k} exit rc={rc}; see {proc.log_path}"
                )
            out[k] = proc.stats()
        return out

    def acked(self, p: int) -> int:
        from ..transport.spool import read_spool_cursor

        return read_spool_cursor(
            self.spool_dir, partition_queue(self.base_queue, p)
        )

    def wait_acked(self, p: int, n: int, timeout_s: float = 120.0) -> int:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            got = self.acked(p)
            if got >= n:
                return got
            time.sleep(0.02)
        raise TimeoutError(
            f"partition p{p} cursor stuck at {self.acked(p)} < {n}"
        )

    # -- observability -------------------------------------------------------
    def _mark_event(self, ev: str, *, shard: Optional[int] = None, **fields) -> None:
        if not self.event_log:
            return
        path = (
            self.procs[shard].event_log_path if shard is not None
            else os.path.join(self.workdir, "events-fleet.jsonl")
        )
        fields.update(ev=ev, ts=time.time())
        if shard is not None:
            fields["shard"] = shard
        with open(path, "a", encoding="utf-8") as fh:
            fh.write(json.dumps(fields, separators=(",", ":")) + "\n")

    def merged_events(self) -> List[dict]:
        """Every shard's protocol event log + the harness's fleet markers,
        merged by wall clock — the input of conformance.check_fleet_trace."""
        from ..analysis.protocol.conformance import read_event_log

        assert self.event_log, "harness built without event_log"
        events: List[dict] = []
        for k, proc in self.procs.items():
            for ev in read_event_log(proc.event_log_path):
                ev.setdefault("shard", k)
                events.append(ev)
        fleet_log = os.path.join(self.workdir, "events-fleet.jsonl")
        events.extend(read_event_log(fleet_log))
        events.sort(key=lambda e: e.get("ts", 0.0))
        return events

    def shard_events(self, k: int) -> List[dict]:
        from ..analysis.protocol.conformance import read_event_log

        return read_event_log(self.procs[k].event_log_path)

    def close(self) -> None:
        for proc in self.procs.values():
            proc.kill9()
        self._producer_channel.close()


# ---------------------------------------------------------------------------
# The shard child process
# ---------------------------------------------------------------------------


def _parse_lags(spec: str) -> List[dict]:
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        out.append({"LAG": int(part), "THRESHOLD": 20.0, "INFLUENCE": 0.1})
    return out


def _shard_main(argv=None) -> int:
    """One fleet shard: the production WorkerApp (fleet mode, at-least-once,
    per-partition queues) over the shared spool. Everything between the
    spool and the engine snapshot is the REAL production path; the only
    harness-specific parts are the control-file poll and the DONE/stats
    files."""
    import argparse

    ap = argparse.ArgumentParser(prog="apmbackend_tpu.parallel.fleet --shard")
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--shard-id", type=int, required=True)
    ap.add_argument("--shards", type=int, required=True)
    ap.add_argument("--partitions", type=int, default=0)
    ap.add_argument("--capacity", type=int, default=64)
    ap.add_argument("--samples-per-bucket", type=int, default=64)
    ap.add_argument("--save-every-s", type=float, default=0.4)
    ap.add_argument("--feed-delay-s", type=float, default=0.05)
    ap.add_argument("--checkpoint-mode", default="delta", choices=("full", "delta"))
    ap.add_argument("--compact-every", type=int, default=0)
    ap.add_argument("--partition-key", default="service")
    ap.add_argument("--lags", default="6")
    ap.add_argument("--queue", default="transactions")
    ap.add_argument("--event-log", action="store_true")
    ap.add_argument("--metrics", action="store_true")
    ap.add_argument("--fast-alerts", action="store_true")
    args = ap.parse_args(argv)

    from ..config import default_config
    from ..runtime.module_base import ModuleRuntime
    from ..runtime.worker import WorkerApp
    from ..transport.base import QueueManager
    from ..transport.spool import SpoolChannel

    workdir = os.path.abspath(args.workdir)
    spool_dir = os.path.join(workdir, "spool")
    k = args.shard_id
    cfg = default_config()
    eng = cfg["tpuEngine"]
    eng["serviceCapacity"] = args.capacity
    eng["samplesPerBucket"] = args.samples_per_bucket
    eng["deliveryMode"] = "atLeastOnce"
    eng["deliveryFeedMaxDelaySeconds"] = args.feed_delay_s
    eng["metricsPort"] = 0 if args.metrics else None
    cfg["fleet"].update({
        "shards": args.shards,
        "partitions": args.partitions,
        "partitionKey": args.partition_key,
        "shardId": None,  # APM_SHARD_ID env wins (set by the harness)
        "epochStallSeconds": 300.0,
    })
    if args.checkpoint_mode == "delta":
        eng["checkpointMode"] = "delta"
        # {shard}-templating exercised on purpose: one config, N chains
        eng["checkpointChainDir"] = os.path.join(workdir, "chain-shard{shard}")
        eng["resumeFileFullPath"] = None
        eng["checkpointCompactEveryEpochs"] = args.compact_every
        eng["checkpointWriteRetryBaseSeconds"] = 0.05
        eng["checkpointWriteRetryMaxSeconds"] = 0.5
    else:
        eng["resumeFileFullPath"] = os.path.join(
            workdir, "engine-shard{shard}.resume.npz"
        )
    if args.event_log:
        eng["protocolEventLog"] = os.path.join(
            workdir, "events-shard{shard}.jsonl"
        )
    cfg["streamCalcZScore"]["defaults"] = _parse_lags(args.lags)
    cfg["streamCalcStats"]["inQueue"] = args.queue
    cfg["streamCalcStats"]["resumeFileSaveFrequencyInSeconds"] = args.save_every_s
    cfg["streamProcessAlerts"]["alertsResumeFileFullPath"] = None
    if args.fast_alerts:
        # chaos/e2e harness mode: page within a couple of bad intervals
        # instead of the production 45-of-60 gating, so a test can force a
        # deterministic alert (and its decision record) with a short spike
        al = cfg["streamProcessAlerts"]
        al["rollingAlertWindowSizeInIntervals"] = 3
        al["requiredNumberBadIntervalsInAlertWindowToTrigger"] = 2
        al["alertOnBothOnly"] = False
        al["perServiceAlertCooldownInMinutes"] = 0
        al["hardMinMsAlertThreshold"] = 1
        al["hardMinTpmAlertThreshold"] = 0
    cfg["logDir"] = None

    runtime = ModuleRuntime(
        "tpuEngine", config=cfg, install_signals=True, console_log=True
    )
    spools: dict = {}

    def factory(direction: str):
        ch = SpoolChannel(spool_dir)
        spools[direction] = ch
        return ch

    runtime.qm = QueueManager(factory, 3600, logger=runtime.logger)
    worker = WorkerApp(runtime)
    consumer = spools["c"]
    consumer.start_pump_thread()

    ctl_path = os.path.join(workdir, f"shard{k}.ctl.json")
    ctl_done = ctl_path + ".done"
    done_path = os.path.join(workdir, "DONE.json")
    stats_path = os.path.join(workdir, f"shard{k}.stats.json")
    resume_out = os.path.join(workdir, f"shard{k}.engine.npz")
    # a restarted child must not re-execute an ALREADY-ACKED control
    # request: resume the sequence from the durable done-file (a pending
    # request with seq above it IS re-executed — that is the channel's
    # kill -9 recovery)
    last_ctl = worker._read_ctl_seq(ctl_done)

    def poll_control() -> None:
        nonlocal last_ctl
        try:
            with open(ctl_path, "r", encoding="utf-8") as fh:
                req = json.load(fh)
        except (OSError, ValueError):
            return
        seq = int(req.get("seq", 0))
        if seq <= last_ctl:
            return
        out = worker._exec_control(req)
        last_ctl = seq
        tmp = ctl_done + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(out, fh, default=repr)
        os.replace(tmp, ctl_done)

    totals = None
    while True:
        poll_control()
        if totals is None and os.path.exists(done_path):
            try:
                with open(done_path, "r", encoding="utf-8") as fh:
                    totals = json.load(fh)["totals"]
            except Exception:
                totals = None
        if totals is not None:
            owned = [
                partition_queue(args.queue, p) for p in worker.owned_partitions()
            ]
            delivered_all = all(
                consumer.delivered_count(q) >= int(totals.get(q, 0))
                for q in owned
            )
            if delivered_all:
                worker.save_state()  # final epoch commit drains + acks
                if all(
                    consumer.acked_count(q) >= int(totals.get(q, 0))
                    for q in owned
                ):
                    break
        time.sleep(0.02)

    consumer.stop()
    worker.shutdown()  # final save_state + ack inside
    with worker._driver_lock:
        worker.driver.save_resume(resume_out)
        tracer = worker.driver._tracer
        ticks = list(tracer.ring) if tracer is not None else []
        emit_lat = getattr(worker.driver, "_m_emit_lat", None)
        e2e = None
        if emit_lat is not None and emit_lat._count:
            from ..obs import histogram_quantile

            cum = 0
            pts = []
            for bound, c in zip(emit_lat.bounds, emit_lat._counts):
                cum += c
                pts.append((bound, cum))
            pts.append((float("inf"), emit_lat._count))
            e2e = {
                "p50_ms": round(histogram_quantile(pts, 0.5) * 1000, 3),
                "p95_ms": round(histogram_quantile(pts, 0.95) * 1000, 3),
                "count": emit_lat._count,
            }
        stats = {
            "shard": k,
            "epoch": worker._delivery_epoch,
            "deduped_total": worker._deduped_total,
            "unacked": len(worker._epoch_tokens),
            "services": worker.driver.registry.count,
            "capacity": worker.driver.cfg.capacity,
            "lags": [spec.lag for spec in worker.driver.cfg.lags],
            "latest_label": worker.driver._latest_label,
            "owned_partitions": worker.owned_partitions(),
            "partition_mismatches": worker._partition_mismatch_total,
            "rebalances": worker._rebalances_total,
            "checkpoint_mode": args.checkpoint_mode,
            "chain_epoch": (
                worker._ckpt_chain.tail_epoch
                if worker._ckpt_chain is not None else None
            ),
            "ticks": ticks,
            "e2e_ingest_to_emit": e2e,
        }
    tmp = stats_path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(stats, fh, default=repr)
    os.replace(tmp, stats_path)
    runtime.stop_timers()
    return 0


if __name__ == "__main__":
    import sys

    if "--shard" in sys.argv:
        sys.argv.remove("--shard")
        sys.exit(_shard_main(sys.argv[1:]))
    raise SystemExit("usage: python -m apmbackend_tpu.parallel.fleet --shard ...")
