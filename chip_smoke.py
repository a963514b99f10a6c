#!/usr/bin/env python
"""Bring-up smoke: the APM pipeline's main path on a TPU, checked end to end.

Default phase (one chip, one process; what the driver runs):
  1. assert a TPU: there is no CPU path;
  2. rebuild ``native/`` from its committed sources (``make -B``) and report
     which native pieces loaded;
  3. replay a seeded fleet through ``StandalonePipeline`` (what
     ``python -m apmbackend_tpu standalone --replay`` runs) on the default
     config with ``serviceCapacity`` 8192: logs -> parser -> broker -> worker
     intake -> device tick -> alert rules -> sqlite sink. The device must
     ingest exactly the records the generator wrote, nothing may be dropped
     or evicted on the way, every record must reach the sink's tx table or
     the engine's resumable backlog, every key must have FullStat rows, and
     a seeded sample of keys must equal the float64 oracle
     ``tests/golden.GoldenStats`` fed those records;
  4. ``tools/demo.run_demo``: the injected regression alerts, nothing else.

``--chips 4`` runs only the service-sharded step (``make_sharded_ingest`` +
``make_sharded_step``) on a 4-chip mesh at 10,240 services and compares every
row's emission with the one-chip ``make_engine_step`` on the same records.

Set-up/compile seconds, wall seconds per phase and peak HBM bytes are printed
as diagnostics. The last line of stdout is the verdict
``{"ok": true, "device": {...}}``; any failed phase exits non-zero without it.
The phase functions take their sizes as arguments, so
tests/test_chip_smoke_rehearsal.py runs them small on the CPU.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# The headline deployment. The fixture writes each service name under two
# record kinds (EJB CommonTiming ``S:<name>`` in server.log, standard
# CommonTiming ``<name>`` in the app log), so 35 servers x 117 names give
# 8,190 (server, service) keys in 8,192 rows.
SERVERS = 35
SERVICES = 117
TRANSACTIONS = 800_000
INTERVALS = 96  # 10 s buckets of log time: three 30-interval windows + buffer
CAPACITY = 8192
SAMPLED_KEYS = 64
# --chips 4: the podshard cell's shape (benchmarks/bench_podshard.py)
POD_SERVICES = 10_240
POD_TICKS = 36
POD_RECORDS_PER_ROW = 3


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(ok, msg: str) -> None:
    """A result check that also holds under ``python -O``."""
    if not ok:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile events: the set-up seconds of a phase."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_kw):
        if name.endswith("backend_compile_duration"):
            self.seconds += secs
            self.count += 1

    def take(self):
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


def require_tpu(n_chips: int):
    """The visible devices, or SystemExit when they are not ``n_chips`` TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
                         "this script has no CPU path")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: {n_chips} chips asked for, {len(devices)} visible")
    return devices[:n_chips]


# -------------------------------------------------------------- native ----

def build_native(build_dir: str) -> dict:
    """Rebuild native/ from its sources into a fresh ``build_dir`` (make -B:
    nothing built elsewhere, e.g. with another machine's -march=native, is
    reused) and load the native pieces from there. Returns {piece: loaded}."""
    shutil.rmtree(build_dir, ignore_errors=True)
    subprocess.run(["make", "-B", "-j4", "-C", os.path.join(ROOT, "native"), f"BUILD={build_dir}"],
                   check=True, stdout=subprocess.DEVNULL)
    from apmbackend_tpu import native

    native.use_build(build_dir)
    return {
        "tail": native.tail_binary_path() is not None,
        "ring": native._load_ring_lib() is not None,
        "decoder": native._load_decode_lib() is not None,
        "parser": native.have_native_parser(),
        "percentile": native.have_native_percentiles(),
        "rebuild": native.have_native_rebuild(),
    }


# -------------------------------------------------------------- replay ----

def write_fleet(log_dir: str, *, servers: int, services: int, transactions: int,
                intervals: int, seed: int) -> list:
    """Seeded fleet logs, one directory per server (``<log_dir>/jvmNN/``).
    Returns the records the parser makes of them, as the generator wrote
    them: ``(server, service, route, end epoch ms, elapsed ms)``."""
    from apmbackend_tpu.ingest.replay import write_fixture_logs

    names = tuple(f"svc{i:03d}" for i in range(services))
    per_server = transactions // servers
    written = []
    for s in range(servers):
        server = f"jvm{s:02d}"
        recs: list = []
        write_fixture_logs(os.path.join(log_dir, server), n_transactions=per_server,
                           services=names, seed=seed + s, server=server,
                           tx_per_bucket=per_server / intervals, records=recs)
        written.extend((server, *r) for r in recs)
    return written


def replay_config(workdir: str, capacity: int) -> dict:
    """config.py defaults; only the capacity, the paths and the sqlite sink
    change (as tools/demo.build_demo_config sets them)."""
    from apmbackend_tpu.config import default_config

    cfg = default_config()
    cfg["logDir"] = os.path.join(workdir, "logs")
    eng = cfg["tpuEngine"]
    eng["serviceCapacity"] = capacity
    eng["resumeFileFullPath"] = os.path.join(workdir, "engine.resume.npz")
    cfg["streamProcessAlerts"]["alertsResumeFileFullPath"] = os.path.join(workdir, "alerts.resume")
    db = cfg["streamInsertDb"]
    db["dbBackend"] = "sqlite"
    db["dbFileFullPath"] = os.path.join(workdir, "apm.db")
    db["bufferResumeFileFullPath"] = os.path.join(workdir, "db.resume")
    pt = cfg["streamParseTransactions"]
    pt["tailPauseFileFullPath"] = os.path.join(workdir, "PAUSE")
    pt["serverFromPathPattern"] = r"/([^/]+)/[^/]+$"  # the server's directory
    pt["serverPathComponentIndex"] = None
    return cfg


def _tap_ingest(driver, records: list) -> None:
    """Record every batch the driver scatters to the device, in order."""
    import numpy as np

    inner = driver._ingest

    def ingest(state, cfg, rows, labels, elaps, valid):
        v = np.asarray(valid)
        records.append((np.asarray(rows)[v], np.asarray(labels)[v], np.asarray(elaps)[v]))
        return inner(state, cfg, rows, labels, elaps, valid)

    driver._ingest = ingest


def check_ingested(records, keys, written, interval_ms: int) -> int:
    """The device ingested exactly the engine-bound records the generator
    wrote: the same (key, bucket, elapsed) multiset. Returns the count."""
    from collections import Counter

    want = Counter((server, service, end_ms // interval_ms, elapsed)
                   for server, service, route, end_ms, elapsed in written if route == "stats")
    got = Counter()
    for rows, labels, elaps in records:
        for r, lbl, e in zip(rows.tolist(), labels.tolist(), elaps.tolist()):
            got[(*keys[r], lbl, int(e))] += 1
    lost, extra = want - got, got - want
    check(not lost and not extra,
          f"device ingested {sum(got.values())} records, the generator wrote {sum(want.values())}: "
          f"{sum(lost.values())} missing (e.g. {list(lost)[:3]}), "
          f"{sum(extra.values())} not written (e.g. {list(extra)[:3]})")
    return sum(got.values())


def check_tx_rows(db_path: str, resume_path: str, written) -> dict:
    """Every written record is a row of the sink's tx table or a line of the
    engine's saved tx backlog (rows past the last tick's edge wait there,
    as the reference keeps its heap in the resume file), per key."""
    from collections import Counter
    import sqlite3

    import numpy as np

    want = Counter((server, service) for server, service, *_ in written)
    con = sqlite3.connect(db_path)
    try:
        sunk = Counter(dict(((sv, svc), n) for sv, svc, n in con.execute(
            "SELECT server, service, COUNT(*) FROM tx GROUP BY server, service")))
    finally:
        con.close()
    with np.load(resume_path, allow_pickle=True) as data:
        pending = Counter(tuple(line.split("|")[1:3]) for line in data["pending_tx"].tolist())
    got = sunk + pending
    check(got == want,
          f"tx rows {sum(sunk.values())} + pending {sum(pending.values())} != "
          f"{sum(want.values())} written; e.g. "
          f"{[(k, got[k], want[k]) for k in (set(got) | set(want)) if got[k] != want[k]][:3]}")
    return {"tx_rows": sum(sunk.values()), "tx_pending": sum(pending.values())}


def golden_rows(records, keys, sample, stats_cfg) -> dict:
    """{(server, service, ts_ms): golden row} for the sampled rows, from the
    float64 oracle fed the ingested records in the device's arrival order
    (``check_ingested`` has shown they are the written records). Records of
    other keys still advance the oracle's clock (they open buckets), under a
    sentinel key whose rows are dropped."""
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from golden import GoldenStats

    g = GoldenStats(window_sz=stats_cfg.window_sz, buffer_sz=stats_cfg.buffer_sz,
                    interval_len=stats_cfg.interval_len_s)
    wanted = set(int(r) for r in sample)
    tick = ("\x00", "\x00")
    out = {}
    for rows, labels, elaps in records:
        for r, lbl, e in zip(rows.tolist(), labels.tolist(), elaps.tolist()):
            if r in wanted:
                server, service = keys[r]
            elif lbl > g.latest_bucket:
                server, service = tick
            else:
                continue
            for row in g.add(server, service, lbl * 10000, int(e)):
                if row["server"] != tick[0]:
                    out[(row["server"], row["service"], row["ts"])] = row
    return out


def _wire_equal(got, want: float, digits: int) -> bool:
    """Device value (f32, wire-rounded) vs the float64 oracle at the wire
    rounding. On an exact rounding tie f32 and f64 may round either way, so
    there both neighbours count."""
    if got is None or want is None or math.isnan(want):
        return (got is None) == (want is None or math.isnan(want))
    scale = 10.0 ** digits
    up = math.floor(want * scale + 0.5) / scale
    ok = [up]
    if abs(want * scale - math.floor(want * scale) - 0.5) < 1e-6:
        ok.append(up - 1.0 / scale)
    return any(abs(got - w) <= 1e-6 * max(1.0, abs(w)) for w in ok)


def check_sink(db_path: str, keys, gold: dict, sample_keys) -> dict:
    """Every key has FullStat rows; every sampled key's rows equal the oracle."""
    import sqlite3
    from datetime import datetime

    sampled = set(sample_keys)
    con = sqlite3.connect(db_path)
    try:
        with_rows = set()
        got = []
        for row in con.execute("SELECT server, service, timestamp, lag, tpm, stats FROM stats"):
            key = (row[0], row[1])
            with_rows.add(key)
            if key in sampled:
                got.append(row)
    finally:
        con.close()
    missing = [k for k in keys if tuple(k) not in with_rows]
    check(not missing, f"{len(missing)} keys have no FullStat rows, e.g. {missing[:3]}")
    seen = set()
    for server, service, stamp, lag, tpm, blob in got:
        ts = int(datetime.fromisoformat(stamp.replace("Z", "+00:00")).timestamp() * 1000)
        want = gold.get((server, service, ts))
        if want is None:
            continue  # a tick after the oracle's last record (the final drain)
        st = json.loads(blob)
        for name, value, digits in (("tpm", tpm, 2), ("average", st["average"], 1),
                                    ("per75", st["per75"], 1), ("per95", st["per95"], 1)):
            check(_wire_equal(value, want[name], digits),
                  f"{server}/{service} ts={stamp} lag={lag} {name}: "
                  f"device {value} != oracle {want[name]}")
        seen.add((server, service, ts))
    lost = sorted(k for k in gold if k not in seen)
    check(not lost, f"no sink row for oracle rows {lost[:3]}")
    with_data = sum(gold[k]["count"] > 0 for k in seen)
    check(with_data > 0, "no sampled FullStat row with transactions was compared")
    return {"keys_with_rows": len(with_rows), "oracle_rows": len(seen),
            "oracle_rows_with_tx": with_data}


def replay_phase(workdir: str, *, servers: int, services: int, transactions: int,
                 intervals: int, capacity: int, sampled: int, seed: int) -> dict:
    import numpy as np

    from apmbackend_tpu.standalone import StandalonePipeline

    t0 = time.perf_counter()
    log_dir = os.path.join(workdir, "fleet")
    written = write_fleet(log_dir, servers=servers, services=services,
                          transactions=transactions, intervals=intervals, seed=seed)
    t_gen = time.perf_counter() - t0

    cfg = replay_config(workdir, capacity)
    records: list = []
    t0 = time.perf_counter()
    pipe = StandalonePipeline(config=cfg, tail=False, install_signals=False)
    evicted: list = []  # lines a full producer buffer dropped: a lost write
    for rt in (pipe.lead, pipe.parser_rt, pipe.sink_rt, pipe.jmx_rt):
        rt.qm.on("overflow", lambda _queue, n: evicted.append(n))
    try:
        drv = pipe.worker.driver
        _tap_ingest(drv, records)
        fed = pipe.replay(log_dir)
        t_replay = time.perf_counter() - t0
        keys = list(drv.registry.rows())
        stats_cfg = drv.cfg.stats
        executor = drv._step.kind
        dropped = pipe.worker.intake_dropped
    finally:
        pipe.shutdown()
    check(not evicted, f"producer buffers evicted {sum(evicted)} lines")
    check(dropped == 0, f"the worker's intake dropped {dropped} lines")
    check(keys, "no key reached the engine")
    check(len(keys) <= capacity, f"{len(keys)} keys in {capacity} rows")

    rng = np.random.RandomState(seed)
    sample = rng.choice(len(keys), size=min(sampled, len(keys)), replace=False)
    t0 = time.perf_counter()
    ingested = check_ingested(records, keys, written, stats_cfg.interval_len_s * 1000)
    db_path = cfg["streamInsertDb"]["dbFileFullPath"]
    result = check_tx_rows(db_path, cfg["tpuEngine"]["resumeFileFullPath"], written)
    gold = golden_rows(records, keys, sample, stats_cfg)
    result.update(check_sink(db_path, keys, gold, [tuple(keys[i]) for i in sample]))
    t_check = time.perf_counter() - t0
    result.update({
        "transactions": transactions // servers * servers, "records": len(written),
        "lines": fed, "keys": len(keys), "capacity": capacity, "ingested": ingested,
        "executor": executor, "generate_s": t_gen, "replay_s": t_replay, "check_s": t_check,
    })
    return result


def demo_phase(workdir: str) -> int:
    import io

    from apmbackend_tpu.tools.demo import run_demo

    out = io.StringIO()
    rc = run_demo(workdir, out=out)
    for line in out.getvalue().splitlines():
        say(line)
    return rc


# ------------------------------------------------------------- 4 chips ----

def sharded_phase(devices, *, services: int, ticks: int, per_row: int, seed: int) -> dict:
    """The service-sharded step over ``devices`` vs the one-chip staged step
    on the same records, every row of every tick."""
    import jax
    import numpy as np

    from apmbackend_tpu.parallel import (
        ShardedRebuildScheduler,
        make_sharded_ingest,
        make_sharded_step,
        route_batch,
        shard_rows,
    )
    from apmbackend_tpu.parallel.mesh import SERVICE_AXIS
    from apmbackend_tpu.pipeline import (
        RebuildScheduler,
        engine_ingest,
        engine_init,
        make_demo_engine,
        make_engine_step,
    )
    from jax.sharding import Mesh

    n = len(devices)
    cfg, state_one, params = make_demo_engine(
        services, 128, [(360, 20.0, 0.1), (8640, 15.0, 0.0)])
    mesh = Mesh(np.array(devices), (SERVICE_AXIS,))
    pod_step = make_sharded_step(mesh, cfg)
    pod_ingest = make_sharded_ingest(mesh, cfg)
    pod_sched = ShardedRebuildScheduler(mesh, cfg)
    pod_state = shard_rows(engine_init(cfg), mesh)  # no buffer shared with state_one
    pod_params = shard_rows(params, mesh)
    one_step = make_engine_step(cfg)
    one_ingest = jax.jit(engine_ingest, static_argnums=1, donate_argnums=(0,))
    one_sched = RebuildScheduler(cfg)
    state_one = jax.device_put(state_one, devices[0])
    params = jax.device_put(params, devices[0])

    rng = np.random.RandomState(seed)
    B = services * per_row
    label = 170_000_000
    compared = 0
    for _ in range(ticks):
        label += 1
        em_p, roll, pod_state = pod_step(pod_state, label, pod_params)
        em_1, state_one = one_step(state_one, label, params)
        pod_state = pod_sched.step(pod_state)
        state_one = one_sched.step(state_one)
        a, b = jax.device_get((em_p, em_1))
        np.testing.assert_array_equal(a.count, b.count)
        np.testing.assert_allclose(a.tpm, b.tpm, rtol=1e-6)
        np.testing.assert_allclose(np.nan_to_num(a.average, nan=-1),
                                   np.nan_to_num(b.average, nan=-1), rtol=1e-5)
        for la, lb in zip(a.lags, b.lags):
            np.testing.assert_array_equal(la.signal, lb.signal)
            np.testing.assert_array_equal(la.trigger, lb.trigger)
        check(int(roll.total_tx) == int(b.count.sum()), "pod rollup total_tx != one-chip count")
        compared += int(b.count.shape[0])

        rows = rng.randint(0, services, B).astype(np.int32)
        labels = np.full(B, label, np.int32)
        elaps = rng.randint(50, 2000, B).astype(np.float32)
        valid = np.ones(B, bool)
        r, l, e, v, dropped = route_batch(rows, labels, elaps, valid, capacity=cfg.capacity,
                                          n_shards=n, batch_per_shard=2 * B // n)
        check(dropped == 0, f"route_batch dropped {dropped} records")
        pod_state = pod_ingest(pod_state, r, l, e, v)
        state_one = one_ingest(state_one, cfg, rows, labels, elaps, valid)

    placed = {}
    for shard in pod_state.stats.samples.addressable_shards:
        placed[shard.device.id] = shard.data.shape[0]
    want = {d.id: cfg.capacity // n for d in devices}
    check(placed == want, f"rows per device {placed}, want {want}")
    return {"services": cfg.capacity, "ticks": ticks, "rows_compared": compared,
            "rows_per_device": placed, "executor": pod_step.kind}


# ---------------------------------------------------------------- main ----

def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--transactions", type=int, default=TRANSACTIONS)
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    from apmbackend_tpu.utils.compile_cache import use_compile_cache

    say(f"device {devices[0].device_kind} x{len(devices)}; "
        f"compile cache {use_compile_cache() or 'off'}")
    clock = CompileClock()

    if args.chips == 4:
        t0 = time.perf_counter()
        res = sharded_phase(devices, services=POD_SERVICES, ticks=POD_TICKS,
                            per_row=POD_RECORDS_PER_ROW, seed=args.seed)
        compile_s, n_compiles = clock.take()
        say(f"sharded vs one chip: {json.dumps(res)}")
        say(f"sharded phase wall {time.perf_counter() - t0:.3f} s, "
            f"compile {compile_s:.3f} s ({n_compiles} programs), peak bytes "
            f"{[peak_bytes(d) for d in devices]}")
    else:
        t0 = time.perf_counter()
        pieces = build_native(os.path.join(ROOT, "native", "build"))
        say(f"native rebuilt in {time.perf_counter() - t0:.3f} s; loaded {json.dumps(pieces)}")
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
            t0 = time.perf_counter()
            res = replay_phase(os.path.join(work, "replay"), servers=SERVERS,
                               services=SERVICES, transactions=args.transactions,
                               intervals=INTERVALS, capacity=CAPACITY,
                               sampled=SAMPLED_KEYS, seed=args.seed)
            compile_s, n_compiles = clock.take()
            say(f"replay: {json.dumps(res)}")
            say(f"replay phase wall {time.perf_counter() - t0:.3f} s, compile "
                f"{compile_s:.3f} s ({n_compiles} programs), peak bytes "
                f"{peak_bytes(devices[0])}")
            t0 = time.perf_counter()
            rc = demo_phase(os.path.join(work, "demo"))
            compile_s, n_compiles = clock.take()
            say(f"demo phase wall {time.perf_counter() - t0:.3f} s, compile "
                f"{compile_s:.3f} s ({n_compiles} programs), rc {rc}")
            if rc != 0:
                raise SystemExit("chip_smoke: the demo did not detect exactly its injected regression")
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
