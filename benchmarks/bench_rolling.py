"""BASELINE.json configs[1]: multi-service rolling baseline, 100 services.

The stream_calc_stats role at the reference's real key scale: 100 services'
elapsed-time buckets ingested per 10 s interval, windowed TPM/avg/p75/p95 plus
one-lag z-score baselining per tick. Reports metrics/sec/chip against the
per-chip north star.

Also the telemetry-overhead proof (ISSUE 2 acceptance): the measured loop is
run twice — telemetry OFF (bare), then ON with the per-tick stage histograms
recording into a live registry, a TelemetryServer exporting it, and a
background scraper hitting /metrics at 2 Hz throughout — and the headline
reports the ON/OFF throughput delta. The obs plane must stay under 2%.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .common import latency_stats_ms, per_chip_unit, result


def _measure(ticks: int, tx_per_tick: int, services: int, capacity: int, telemetry: bool) -> dict:
    import jax

    from apmbackend_tpu.pipeline import (
        RebuildScheduler,
        engine_ingest,
        make_demo_engine,
        make_engine_step,
    )

    cfg, state, params = make_demo_engine(capacity, 64, [(360, 20.0, 0.1)])
    # auto executor: this shape resolves to the FUSED single/two-dispatch
    # tick (pipeline.make_fused_step — the r5 dispatch-floor fix); the
    # staggered rebuild is folded INTO the tick program there, so it is
    # still executed and charged every measured tick
    tick = make_engine_step(cfg)
    ingest = jax.jit(engine_ingest, static_argnums=1, donate_argnums=(0,))
    # staged fallback: staggered rebuild executed + charged in the measured
    # loop via the separate scheduler (r4 VERDICT)
    sched = None if tick.rebuild_integrated else RebuildScheduler(cfg)

    tracer = None
    server = None
    scraper_stop = None
    scrapes = [0]
    if telemetry:
        from apmbackend_tpu.obs import MetricsRegistry, TelemetryServer, TickTracer

        registry = MetricsRegistry()
        tracer = TickTracer(registry)
        server = TelemetryServer(registry, port=0)
        server.start()
        scraper_stop = threading.Event()

        def _scrape_loop():
            import urllib.request

            while not scraper_stop.is_set():
                try:
                    with urllib.request.urlopen(f"{server.url}/metrics", timeout=2) as r:
                        r.read()
                    scrapes[0] += 1
                except Exception:
                    pass
                scraper_stop.wait(0.5)

        threading.Thread(target=_scrape_loop, daemon=True).start()

    rng = np.random.RandomState(0)
    label = 170_000_000

    def batch(lbl):
        rows = rng.randint(0, services, tx_per_tick).astype(np.int32)
        labels = np.full(tx_per_tick, lbl, np.int32)
        elaps = (200 + 50 * rng.rand(tx_per_tick)).astype(np.float32)
        return rows, labels, elaps, np.ones(tx_per_tick, bool)

    for _ in range(3):  # warmup/compile
        label += 1
        em, state = tick(state, label, params)
        jax.block_until_ready(em.tpm)
        if sched is not None:
            state = sched.step(state)
        state = ingest(state, cfg, *batch(label))
    jax.block_until_ready(state.stats.counts)

    lat = []
    rebuilds = []
    t_start = time.perf_counter()
    for _ in range(ticks):
        label += 1
        t0 = time.perf_counter()
        em, state = tick(state, label, params)
        jax.block_until_ready(em.lags[0].trigger)
        t1 = time.perf_counter()
        lat.append(t1 - t0)
        rb = 0.0
        if sched is not None:
            state = sched.step_synced(state)
            rb = time.perf_counter() - t1
            rebuilds.append(rb)
        if tracer is not None:
            # the PipelineDriver's per-tick record: dispatch+compute under
            # "dispatch" (this loop has no separate emit fan-out)
            tracer.record(label, {"dispatch": t1 - t0, "rebuild": rb})
        state = ingest(state, cfg, *batch(label))
    jax.block_until_ready(state.stats.counts)
    wall = time.perf_counter() - t_start

    if scraper_stop is not None:
        scraper_stop.set()
    if server is not None:
        server.stop()

    metrics_per_tick = capacity * 3 * len(cfg.lags)
    return {
        "throughput": metrics_per_tick * ticks / (sum(lat) + sum(rebuilds)),
        "lat": lat,
        "rebuilds": rebuilds,
        "wall": wall,
        "tick": tick,
        "sched": sched,
        "scrapes": scrapes[0],
    }


def _measure_delivery(quick: bool) -> dict:
    """ISSUE 3 acceptance: at-least-once epoch cadence ON vs OFF.

    The same transport->driver loop twice at the reference's real density —
    at-most-once (ack-on-receipt, no commits) vs at-least-once (manual-ack
    consumer, msg_id dedup window, and every 6 ticks the full epoch commit:
    flush -> atomic npz checkpoint with the delivery tree -> batch ack).
    Reports lines/s both ways; the delta IS the durability price."""
    import os
    import shutil
    import tempfile
    from collections import deque

    from apmbackend_tpu.config import default_config
    from apmbackend_tpu.entries import EntryFactory
    from apmbackend_tpu.pipeline import PipelineDriver
    from apmbackend_tpu.transport.base import QueueManager
    from apmbackend_tpu.transport.memory import MemoryBroker, MemoryChannel

    ticks = 8 if quick else 48
    per_tick = 128  # ~reference density over ~100 services
    commit_every = 6
    cfg = default_config()
    cfg["tpuEngine"]["serviceCapacity"] = 128
    cfg["tpuEngine"]["samplesPerBucket"] = 64
    cfg["streamCalcZScore"]["defaults"] = [
        {"LAG": 360, "THRESHOLD": 20.0, "INFLUENCE": 0.1}
    ]
    base = 170_100_000
    rng = np.random.RandomState(1)
    stream = []
    for t in range(ticks + 2):
        for i in range(per_tick):
            e = int(rng.randint(50, 900))
            stream.append(
                f"tx|jvm{i % 4}|svc{i % 100:03d}|b{t}-{i}|1|{(base + t) * 10000 - e}|"
                f"{(base + t) * 10000 + i}|{e}|Y"
            )

    def one(mode: str) -> float:
        from apmbackend_tpu.deltachain import DeltaChain

        tmpd = tempfile.mkdtemp(prefix="bench_alo_")
        resume = os.path.join(tmpd, "engine.npz")
        drv = PipelineDriver(cfg, capacity=128)
        chain = None
        if mode == "alo_delta":
            # the worker's checkpointMode: "delta" epoch commit — dirty-cell
            # delta append instead of the full npz rewrite
            drv.enable_delta_capture()
            chain = DeltaChain(os.path.join(tmpd, "chain"))
            chain.initialize(drv._capture_resume_arrays(None), epoch=0)
        fac = EntryFactory()
        broker = MemoryBroker()
        prod = QueueManager(lambda d: MemoryChannel(broker), 3600).get_queue("transactions", "p")
        qm_c = QueueManager(lambda d: MemoryChannel(broker), 3600)
        epochs = 0
        pending: list = []
        added: list = []

        def drain():
            if pending:
                drv.feed_csv_batch(pending)
                pending.clear()

        if mode in ("alo", "alo_batched", "alo_delta"):
            dedup: set = set()
            fifo: deque = deque()
            tokens: list = []
            batched = mode in ("alo_batched", "alo_delta")

            def cb(line, h, tok):
                mid = (h or {}).get("msg_id")
                if mid in dedup:
                    return
                dedup.add(mid)
                fifo.append(mid)
                added.append(mid)
                if len(fifo) > 65536:
                    dedup.discard(fifo.popleft())
                if batched:
                    # the worker's deliveryBatchSize intake: accept now,
                    # bulk-feed at batch-full / commit (runtime/worker.py)
                    pending.append(line)
                    if len(pending) >= 256:
                        drain()
                else:
                    drv.feed(fac.from_csv(line))
                tokens.append(tok)

            cons = qm_c.get_queue("transactions", "c", cb, manual_ack=True)
        else:
            cons = qm_c.get_queue("transactions", "c", lambda line: drv.feed(fac.from_csv(line)))
        cons.start_consume()

        def commit():
            nonlocal epochs, tokens
            epochs += 1
            drain()  # feed precedes checkpoint: token<->effect alignment
            drv.flush()
            if chain is not None:
                drv.save_resume_delta(
                    chain,
                    delivery_delta={
                        "transactions": {"epoch": epochs, "added": list(added),
                                         "evicted": 0}
                    },
                )
                added.clear()
            else:
                drv.save_resume(
                    resume,
                    delivery={"transactions": {"epoch": epochs, "dedup": list(fifo)}},
                )
            cons.ack(tokens)
            tokens = []

        # warmup (compile) on the first 2 ticks, measured loop after
        for line in stream[: 2 * per_tick]:
            prod.write_line(line)
        broker.pump()
        is_alo = mode != "amo"
        if is_alo:
            commit()
        t0 = time.perf_counter()
        for t in range(ticks):
            lo = (t + 2) * per_tick
            for line in stream[lo : lo + per_tick]:
                prod.write_line(line)
            broker.pump()
            if is_alo and (t + 1) % commit_every == 0:
                commit()
        if is_alo:
            commit()  # tail epoch: nothing unacked at the end
        wall = time.perf_counter() - t0
        if is_alo:
            assert broker.unacked_count() == 0
        shutil.rmtree(tmpd, ignore_errors=True)
        return ticks * per_tick / wall

    amo = one("amo")
    alo = one("alo")
    alo_b = one("alo_batched")
    alo_d = one("alo_delta")
    return {
        "lines_per_s_at_most_once": round(amo, 1),
        "lines_per_s_at_least_once": round(alo, 1),
        # the worker's deliveryBatchSize bulk-feed intake (ISSUE 4
        # satellite): same manual-ack/commit cadence, accepted lines
        # reach the engine as 256-line feed_csv_batch calls
        "lines_per_s_at_least_once_batched": round(alo_b, 1),
        # delta-chain epoch commits (ISSUE 7): same batched intake and
        # commit cadence, the checkpoint is a dirty-cell delta append —
        # the gap vs at-most-once IS the remaining durability price
        "lines_per_s_at_least_once_delta": round(alo_d, 1),
        "overhead_pct": round((amo - alo) / amo * 100.0, 2),
        "overhead_batched_pct": round((amo - alo_b) / amo * 100.0, 2),
        "overhead_delta_pct": round((amo - alo_d) / amo * 100.0, 2),
        "commit_every_ticks": commit_every,
        "ticks": ticks,
        "tx_per_tick": per_tick,
        "epoch_cadence_8192": _measure_epoch_cadence(quick),
    }


def _measure_epoch_cadence(quick: bool) -> dict:
    """ISSUE 7 acceptance: epoch (checkpoint + ack) cadence at the
    8192-row shape. Full-snapshot save vs delta commit on an engine whose
    capacity-sized state is what production workers carry; the delta commit
    must be sub-second (it is the whole point of the chain)."""
    import os
    import shutil
    import tempfile

    from apmbackend_tpu.config import default_config
    from apmbackend_tpu.deltachain import DeltaChain
    from apmbackend_tpu.pipeline import PipelineDriver

    rows = 8192
    commits = 3 if quick else 6
    cfg = default_config()
    cfg["tpuEngine"]["serviceCapacity"] = rows
    cfg["streamCalcZScore"]["defaults"] = [
        {"LAG": 360, "THRESHOLD": 20.0, "INFLUENCE": 0.1}
    ]
    tmpd = tempfile.mkdtemp(prefix="bench_epoch_")
    drv = PipelineDriver(cfg, capacity=rows)
    base = 170_300_000
    rng = np.random.RandomState(11)

    def feed(t):
        lines = []
        for i in range(256):
            e = int(rng.randint(50, 900))
            lines.append(
                f"tx|jvm{i % 8}|svc{i % 200:03d}|e{t}-{i}|1|{(base + t) * 10000 - e}|"
                f"{(base + t) * 10000 + i}|{e}|Y"
            )
        drv.feed_csv_batch(lines)

    feed(0)
    feed(1)  # warm-up: compile + registry
    drv.flush()
    full_path = os.path.join(tmpd, "full.npz")
    t0 = time.perf_counter()
    drv.save_resume(full_path)
    full_s = time.perf_counter() - t0

    drv.enable_delta_capture()
    chain = DeltaChain(os.path.join(tmpd, "chain"))
    chain.initialize(drv._capture_resume_arrays(None), epoch=0)
    delta_s = []
    for t in range(2, 2 + commits):
        feed(t)  # one tick + 256 lines per epoch: the sub-second target load
        t0 = time.perf_counter()
        drv.save_resume_delta(chain)
        delta_s.append(time.perf_counter() - t0)
    delta_s.sort()
    p50 = delta_s[len(delta_s) // 2]
    state_bytes = sum(
        a.nbytes for a in drv._capture_resume_arrays(None).values()
        if getattr(a, "dtype", None) is not None and a.dtype != object
    )
    shutil.rmtree(tmpd, ignore_errors=True)
    return {
        "rows": rows,
        "state_bytes": int(state_bytes),
        "full_save_seconds": round(full_s, 4),
        "delta_commit_seconds_p50": round(p50, 4),
        "delta_commit_seconds_max": round(delta_s[-1], 4),
        "sub_second": bool(delta_s[-1] < 1.0),
        "tx_per_epoch": 256,
    }


def _measure_tracing(quick: bool) -> dict:
    """ISSUE 5 acceptance: distributed trace plane ON vs OFF.

    The same transport->driver loop twice — tracing OFF (sample rate 0: no
    headers, no spans, the pre-trace wire) vs ON at the default 1/64 head
    sampling with a live exporter and a background scraper pulling /trace
    at 2 Hz throughout. The consumer registers sampled traces with the
    driver exactly like the worker's feed handoff does, so the measured
    path includes the span recording at every hop. The delta must stay
    under 2%."""
    import threading as _threading
    import urllib.request

    from apmbackend_tpu.config import default_config
    from apmbackend_tpu.entries import EntryFactory
    from apmbackend_tpu.obs import MetricsRegistry, TelemetryServer
    from apmbackend_tpu.obs.trace import Tracer, set_tracer
    from apmbackend_tpu.pipeline import PipelineDriver
    from apmbackend_tpu.transport.base import QueueManager
    from apmbackend_tpu.transport.memory import MemoryBroker, MemoryChannel

    ticks = 8 if quick else 48
    per_tick = 128  # ~reference density over ~100 services
    cfg = default_config()
    cfg["tpuEngine"]["serviceCapacity"] = 128
    cfg["tpuEngine"]["samplesPerBucket"] = 64
    cfg["streamCalcZScore"]["defaults"] = [
        {"LAG": 360, "THRESHOLD": 20.0, "INFLUENCE": 0.1}
    ]
    base = 170_200_000
    rng = np.random.RandomState(3)
    stream = []
    for t in range(ticks + 2):
        for i in range(per_tick):
            e = int(rng.randint(50, 900))
            stream.append(
                f"tx|jvm{i % 4}|svc{i % 100:03d}|t{t}-{i}|1|{(base + t) * 10000 - e}|"
                f"{(base + t) * 10000 + i}|{e}|Y"
            )

    def one(rate: int) -> tuple:
        old = set_tracer(Tracer(module="bench", sample_rate=rate))
        server = None
        stop = None
        scrapes = [0]
        try:
            drv = PipelineDriver(cfg, capacity=128)
            fac = EntryFactory()
            broker = MemoryBroker()
            prod = QueueManager(lambda d: MemoryChannel(broker), 3600).get_queue(
                "transactions", "p"
            )
            qm_c = QueueManager(lambda d: MemoryChannel(broker), 3600)

            def cb(line, h=None):
                if h:
                    tid = h.get("trace_id")
                    if tid is not None:
                        p = line.split("|", 7)
                        drv.note_trace(
                            tid, p[1], p[2], int(p[6]) // 10000, time.time()
                        )
                drv.feed(fac.from_csv(line))

            qm_c.get_queue("transactions", "c", cb).start_consume()

            if rate:
                server = TelemetryServer(port=0, module="bench_tracing")
                server.start()
                stop = _threading.Event()

                def _scrape_loop():
                    while not stop.is_set():
                        try:
                            with urllib.request.urlopen(
                                f"{server.url}/trace?n=256", timeout=2
                            ) as r:
                                r.read()
                            scrapes[0] += 1
                        except Exception:
                            pass
                        stop.wait(0.5)

                _threading.Thread(target=_scrape_loop, daemon=True).start()

            # warmup (compile) on the first 2 ticks, measured loop after
            for line in stream[: 2 * per_tick]:
                prod.write_line(line)
            broker.pump()
            t0 = time.perf_counter()
            for t in range(ticks):
                lo = (t + 2) * per_tick
                for line in stream[lo : lo + per_tick]:
                    prod.write_line(line)
                broker.pump()
            drv.flush()
            wall = time.perf_counter() - t0
            return ticks * per_tick / wall, scrapes[0]
        finally:
            if stop is not None:
                stop.set()
            if server is not None:
                server.stop()
            set_tracer(old)

    off, _ = one(0)
    on, n_scrapes = one(64)
    return {
        "lines_per_s_off": round(off, 1),
        "lines_per_s_on": round(on, 1),
        "sample_rate": 64,
        "overhead_pct": round((off - on) / off * 100.0, 2),
        "trace_scrapes_during_run": n_scrapes,
        "ticks": ticks,
        "tx_per_tick": per_tick,
    }


def _measure_recorder(quick: bool) -> dict:
    """ISSUE 12 acceptance: fleet recorder ON vs OFF.

    The same transport->driver loop twice — recorder OFF (no exporter, the
    bare wire) vs ON with a live exporter and a FleetRecorder persisting
    /metrics + /trace + /decisions into an on-disk TimeSeriesStore at 2 Hz
    throughout. The recorder runs out-of-band (scrape thread + append-mode
    journal), so the hot path should not feel it: the delta must stay
    under 2%."""
    import shutil
    import tempfile

    from apmbackend_tpu.config import default_config
    from apmbackend_tpu.entries import EntryFactory
    from apmbackend_tpu.obs import FleetRecorder, TelemetryServer, TimeSeriesStore
    from apmbackend_tpu.pipeline import PipelineDriver
    from apmbackend_tpu.transport.base import QueueManager
    from apmbackend_tpu.transport.memory import MemoryBroker, MemoryChannel

    ticks = 8 if quick else 48
    per_tick = 128
    cfg = default_config()
    cfg["tpuEngine"]["serviceCapacity"] = 128
    cfg["tpuEngine"]["samplesPerBucket"] = 64
    cfg["streamCalcZScore"]["defaults"] = [
        {"LAG": 360, "THRESHOLD": 20.0, "INFLUENCE": 0.1}
    ]
    base = 170_300_000
    rng = np.random.RandomState(7)
    stream = []
    for t in range(ticks + 2):
        for i in range(per_tick):
            e = int(rng.randint(50, 900))
            stream.append(
                f"tx|jvm{i % 4}|svc{i % 100:03d}|t{t}-{i}|1|{(base + t) * 10000 - e}|"
                f"{(base + t) * 10000 + i}|{e}|Y"
            )

    def one(record: bool) -> tuple:
        server = None
        recorder = None
        store = None
        store_dir = None
        rows = 0
        scrapes = 0
        try:
            drv = PipelineDriver(cfg, capacity=128)
            fac = EntryFactory()
            broker = MemoryBroker()
            prod = QueueManager(lambda d: MemoryChannel(broker), 3600).get_queue(
                "transactions", "p"
            )
            qm_c = QueueManager(lambda d: MemoryChannel(broker), 3600)

            def cb(line):
                drv.feed(fac.from_csv(line))

            qm_c.get_queue("transactions", "c", cb).start_consume()

            if record:
                server = TelemetryServer(port=0, module="bench_recorder")
                server.start()
                store_dir = tempfile.mkdtemp(prefix="bench_recorder_")
                store = TimeSeriesStore(store_dir)
                recorder = FleetRecorder(
                    store,
                    lambda: [("bench", server.url)],
                    interval_s=0.5,
                    self_module="bench",
                )
                recorder.start()

            for line in stream[: 2 * per_tick]:
                prod.write_line(line)
            broker.pump()
            t0 = time.perf_counter()
            for t in range(ticks):
                lo = (t + 2) * per_tick
                for line in stream[lo : lo + per_tick]:
                    prod.write_line(line)
                broker.pump()
            drv.flush()
            wall = time.perf_counter() - t0
            if recorder is not None:
                counts = recorder.status().get("counts", {})
                rows = counts.get("rows_total", 0)
                scrapes = counts.get("scrapes_total", 0)
            return ticks * per_tick / wall, rows, scrapes
        finally:
            if recorder is not None:
                recorder.stop()
            if store is not None:
                store.close()
            if server is not None:
                server.stop()
            if store_dir is not None:
                shutil.rmtree(store_dir, ignore_errors=True)

    off, _, _ = one(False)
    on, n_rows, n_scrapes = one(True)
    return {
        "lines_per_s_off": round(off, 1),
        "lines_per_s_on": round(on, 1),
        "overhead_pct": round((off - on) / off * 100.0, 2),
        "rows_persisted_during_run": n_rows,
        "scrapes_during_run": n_scrapes,
        "ticks": ticks,
        "tx_per_tick": per_tick,
    }


def _measure_transports(quick: bool) -> dict:
    """ISSUE 15 acceptance: the broker is a swappable, measured component.

    Two drills over the same produce->consume loop:

    - throughput per fabric — memory, durable spool, in-process fake-redis
      (wire-faithful Streams semantics), and a real redis server when one
      answers at ``APM_TEST_REDIS_URL`` (skipped otherwise, recorded as
      such — a silent skip would read as coverage);
    - outage recovery — for the fabrics with a broker to kill (fake-redis
      restart, AMQP connection churn via fake_pika): kill mid-stream, keep
      producing into the bounded pause buffer, restart, and report seconds
      from restart to full drain with the unique-delivery count proving
      zero loss through the msg_id dedup window.
    """
    import os
    import shutil
    import sys
    import tempfile

    from apmbackend_tpu.transport.amqp import AmqpChannel
    from apmbackend_tpu.transport.base import QueueManager
    from apmbackend_tpu.transport.memory import MemoryBroker, MemoryChannel
    from apmbackend_tpu.transport.redis_streams import HAVE_REDIS, RedisStreamsChannel
    from apmbackend_tpu.transport.spool import SpoolChannel

    tests_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests")
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    from fake_pika import FakeBroker, make_fake_pika
    from fake_redis import FakeRedisServer, make_fake_redis

    n = 2000 if quick else 20000
    lines = [
        f"tx|jvm{i % 4}|svc{i % 100:03d}|m{i}|1|{1700000000000 + i}|"
        f"{1700000001000 + i}|{i % 900}|Y"
        for i in range(n)
    ]
    deadline_s = 120.0

    def throughput(prod_ch, cons_ch, pump) -> float:
        """lines/s through one fabric: producer write_line -> consumer cb."""
        got = 0

        def cb(_line):
            nonlocal got
            got += 1

        prod = QueueManager(lambda d: prod_ch, 3600).get_queue("bench", "p")
        qm_c = QueueManager(lambda d: cons_ch, 3600)
        cons = qm_c.get_queue("bench", "c", cb)
        cons.start_consume()
        t0 = time.perf_counter()
        for line in lines:
            prod.write_line(line)
        while got < n and time.perf_counter() - t0 < deadline_s:
            if pump() == 0 and prod.buffer_count():
                prod.retry_buffer()
        wall = time.perf_counter() - t0
        return round(n / wall, 1) if got == n else float("nan")

    out: dict = {"lines": n}

    broker = MemoryBroker()
    out["memory_lines_per_s"] = throughput(
        MemoryChannel(broker), MemoryChannel(broker), broker.pump)

    spool_dir = tempfile.mkdtemp(prefix="bench_spool_")
    try:
        spool = SpoolChannel(spool_dir)
        out["spool_lines_per_s"] = throughput(spool, spool, spool.deliver)
        spool.close()
    finally:
        shutil.rmtree(spool_dir, ignore_errors=True)

    def redis_pair(mod):
        kw = dict(redis_module=mod, stream_maxlen=max(n, 1000),
                  reconnect_base_backoff_s=0.0, reconnect_max_backoff_s=0.01)
        return (RedisStreamsChannel("redis://bench", **kw),
                RedisStreamsChannel("redis://bench", **kw))

    server = FakeRedisServer()
    mod = make_fake_redis(server)
    prod_ch, cons_ch = redis_pair(mod)
    out["fake_redis_lines_per_s"] = throughput(
        prod_ch, cons_ch, lambda: prod_ch.pump_once() + cons_ch.pump_once())

    real_url = os.environ.get("APM_TEST_REDIS_URL", "redis://localhost:6379/15")
    if HAVE_REDIS:
        try:
            import redis as _r

            _r.from_url(real_url, socket_connect_timeout=0.5).ping()
            kw = dict(stream_maxlen=max(n, 1000), group=f"bench-{os.getpid()}")
            p, c = (RedisStreamsChannel(real_url, **kw),
                    RedisStreamsChannel(real_url, **kw))
            out["real_redis_lines_per_s"] = throughput(
                p, c, lambda: p.pump_once() + c.pump_once())
            p.close()
            c.close()
        except Exception as e:
            out["real_redis_skipped"] = f"no server at {real_url}: {e}"
    else:
        out["real_redis_skipped"] = "redis-py not installed"

    # frame-mode rows (ISSUE 16): the SAME record stream as packed APF1
    # batches — one write_frames per 512 records, frames-aware consumer
    # counting records straight off the blob. The spool rows are the
    # amortized-commit measurement: line mode pays an append+flush(+fsync)
    # per record, frame mode pays it once per batch.
    from apmbackend_tpu.transport import frames as _frames
    from apmbackend_tpu.transport.shmring import ShmRingChannel

    frame_max = 512
    blobs = [(_frames.encode_lines(lines[i:i + frame_max]),
              min(frame_max, n - i)) for i in range(0, n, frame_max)]

    def frame_throughput(prod_ch, cons_ch, pump) -> float:
        """records/s through one fabric in frameMode (same loop shape as
        ``throughput`` — the per-message unit is a packed batch)."""
        got = [0]

        def cb(payload, _headers):
            got[0] += _frames.frame_count(payload)

        prod = QueueManager(lambda d: prod_ch, 3600).get_queue("benchf", "p")
        cons = QueueManager(lambda d: cons_ch, 3600).get_queue("benchf", "c", cb)
        cons.frames_aware = True
        cons.start_consume()
        t0 = time.perf_counter()
        for blob, cnt in blobs:
            prod.write_frames(blob, cnt)
        while got[0] < n and time.perf_counter() - t0 < deadline_s:
            if pump() == 0 and prod.buffer_count():
                prod.retry_buffer()
        wall = time.perf_counter() - t0
        return round(n / wall, 1) if got[0] == n else float("nan")

    fr: dict = {"batch_records": frame_max, "batches": len(blobs)}

    broker = MemoryBroker()
    fr["memory_lines_per_s"] = frame_throughput(
        MemoryChannel(broker), MemoryChannel(broker), broker.pump)

    for fsync in (False, True):
        key = "spool_fsync" if fsync else "spool"
        spool_dir = tempfile.mkdtemp(prefix=f"bench_{key}_")
        try:
            spool = SpoolChannel(spool_dir, fsync=fsync)
            fr[f"{key}_lines_per_s"] = frame_throughput(
                spool, spool, spool.deliver)
            spool.close()
        finally:
            shutil.rmtree(spool_dir, ignore_errors=True)
    # the fsync'd LINE path is the unamortized comparator for the group
    # commit claim (the plain spool row above flushes without fsync)
    spool_dir = tempfile.mkdtemp(prefix="bench_spool_fsync_line_")
    try:
        spool = SpoolChannel(spool_dir, fsync=True)
        fr["spool_fsync_line_mode_lines_per_s"] = throughput(
            spool, spool, spool.deliver)
        spool.close()
    finally:
        shutil.rmtree(spool_dir, ignore_errors=True)

    server_f = FakeRedisServer()
    pf, cf = redis_pair(make_fake_redis(server_f))
    fr["fake_redis_lines_per_s"] = frame_throughput(
        pf, cf, lambda: pf.pump_once() + cf.pump_once())

    shm_dir = tempfile.mkdtemp(prefix="bench_shmring_")
    try:
        ch = ShmRingChannel(shm_dir, ring_bytes=8 * 1024 * 1024)
        fr["shmring_lines_per_s"] = frame_throughput(ch, ch, ch.pump_once)
        fr["shmring_line_mode_lines_per_s"] = throughput(
            ch, ch, ch.pump_once)
        ch.close()
    finally:
        shutil.rmtree(shm_dir, ignore_errors=True)

    out["frames"] = fr

    def outage_redis() -> dict:
        server = FakeRedisServer()
        mod = make_fake_redis(server)
        prod_ch, cons_ch = redis_pair(mod)
        qm_p = QueueManager(lambda d: prod_ch, 3600,
                            transport_config={"producerBufferMaxLines": n})
        prod = qm_p.get_queue("bench", "p")
        qm_c = QueueManager(lambda d: cons_ch, 3600)
        seen = set()

        def cb(_line, h, tok):
            seen.add((h or {}).get("msg_id"))
            cons_ch.ack([tok])

        cons = qm_c.get_queue("bench", "c", cb, manual_ack=True)
        cons.start_consume()
        half = n // 2
        for line in lines[:half]:
            prod.write_line(line)
        t0 = time.perf_counter()
        while len(seen) < half and time.perf_counter() - t0 < deadline_s:
            cons_ch.pump_once()
        server.kill()
        for line in lines[half:]:
            prod.write_line(line)  # refused sends buffer under the cap
        server.restart()
        t1 = time.perf_counter()
        while len(seen) < n and time.perf_counter() - t1 < deadline_s:
            prod_ch.pump_once()
            if prod.buffer_count():
                prod.retry_buffer()
            cons_ch.pump_once()
        return {
            "recovery_s": round(time.perf_counter() - t1, 3),
            "unique_delivered": len(seen),
            "lost": n - len(seen),
        }

    out["fake_redis_outage"] = outage_redis()

    def outage_amqp() -> dict:
        broker = FakeBroker(block_at=10 ** 9)
        mod = make_fake_pika(broker)
        kw = dict(pika_module=mod, poll_interval_s=0.002,
                  reconnect_base_backoff_s=0.005, reconnect_max_backoff_s=0.02)
        prod_ch = AmqpChannel("amqp://bench", direction="p", **kw)
        cons_ch = AmqpChannel("amqp://bench", direction="c", **kw)
        qm_p = QueueManager(lambda d: prod_ch, 3600,
                            transport_config={"producerBufferMaxLines": n})
        prod = qm_p.get_queue("bench", "p")
        qm_c = QueueManager(lambda d: cons_ch, 3600)
        seen = set()

        def cb(_line, h, tok):
            seen.add((h or {}).get("msg_id"))
            cons_ch.ack([tok])

        cons = qm_c.get_queue("bench", "c", cb, manual_ack=True)
        cons.start_consume()
        half = n // 2
        for line in lines[:half]:
            prod.write_line(line)
        t0 = time.perf_counter()
        while len(seen) < half and time.perf_counter() - t0 < deadline_s:
            time.sleep(0.002)
        broker.kill_connections()
        t1 = time.perf_counter()
        for line in lines[half:]:
            prod.write_line(line)
            if prod.buffer_count():
                prod.retry_buffer()
        while len(seen) < n and time.perf_counter() - t1 < deadline_s:
            if prod.buffer_count():
                prod.retry_buffer()
            time.sleep(0.002)
        rec = {
            "recovery_s": round(time.perf_counter() - t1, 3),
            "unique_delivered": len(seen),
            "lost": n - len(seen),
        }
        prod_ch.close()
        cons_ch.close()
        return rec

    out["amqp_churn_outage"] = outage_amqp()
    return out


def _measure_attribution(quick: bool) -> dict:
    """ISSUE 17 acceptance: wall-clock attribution + frame carriage ON vs OFF.

    The same frames->shmring->driver loop twice, components rebuilt per
    leg (call sites bind their stage clocks at construction):

    - OFF: the PR 16 wire shape — bare APF1 batches, a disabled
      AttributionPlane (the APM_NO_ATTRIB/APM_NO_FRAME_CARRIAGE posture:
      shared no-op clock, call sites skip even the perf_counter pair);
    - ON: APC1 carriage trailers on every batch (per-record delta-millis
      + 1/64 head-sampled trace_id) under a live plane recording
      shmring push/pop/pump, transport send, tick stages, and ring
      occupancy.

    The throughput delta IS the accounting + carriage price; the
    headline gates it under 2%. The ON leg's /attrib snapshot rides
    along so the estimator's verdict for this shape is on record."""
    import shutil
    import tempfile

    from apmbackend_tpu.config import default_config
    from apmbackend_tpu.obs.attrib import AttributionPlane, set_attrib
    from apmbackend_tpu.pipeline import PipelineDriver
    from apmbackend_tpu.transport import frames as _frames
    from apmbackend_tpu.transport.base import QueueManager
    from apmbackend_tpu.transport.shmring import ShmRingChannel

    n_ticks = 6 if quick else 40
    per_tick = 256
    frame_max = 128
    base = 170_200_000
    rng = np.random.RandomState(2)
    cfg = default_config()
    cfg["tpuEngine"]["serviceCapacity"] = 128
    cfg["tpuEngine"]["samplesPerBucket"] = 64
    cfg["streamCalcZScore"]["defaults"] = [
        {"LAG": 360, "THRESHOLD": 20.0, "INFLUENCE": 0.1}
    ]

    lines = []
    for t in range(n_ticks):
        for i in range(per_tick):
            e = int(rng.randint(50, 900))
            lines.append(
                f"tx|jvm{i % 4}|svc{i % 100:03d}|a{t}-{i}|1|"
                f"{(base + t) * 10000 - e}|{(base + t) * 10000 + i}|{e}|Y"
            )
    n = len(lines)
    bare_blobs = [(_frames.encode_lines(lines[i:i + frame_max]),
                   min(frame_max, n - i)) for i in range(0, n, frame_max)]
    carriage_blobs = []
    for idx, (blob, cnt) in enumerate(bare_blobs):
        tid = f"bench-attrib-{idx:x}" if idx % 64 == 0 else ""
        carriage_blobs.append((_frames.append_carriage(
            blob, float(base * 10.0), [(i * 7) % 500 for i in range(cnt)],
            tid), cnt))

    def leg(enabled: bool, blobs) -> tuple:
        plane = AttributionPlane(module="bench_rolling", enabled=enabled)
        prev = set_attrib(plane)
        shm_dir = tempfile.mkdtemp(prefix="bench_attrib_")
        try:
            drv = PipelineDriver(cfg, capacity=128)
            ch = ShmRingChannel(shm_dir, ring_bytes=8 * 1024 * 1024)
            fed = [0]

            def cb(payload, _headers):
                drv.feed_frames(payload)
                fed[0] += 1

            prod = QueueManager(lambda d: ch, 3600).get_queue("bencha", "p")
            cons = QueueManager(lambda d: ch, 3600).get_queue(
                "bencha", "c", cb)
            cons.frames_aware = True
            cons.start_consume()
            t0 = time.perf_counter()
            for blob, cnt in blobs:
                prod.write_frames(blob, cnt)
                ch.pump_once()
            while fed[0] < len(blobs) and time.perf_counter() - t0 < 60.0:
                if ch.pump_once() == 0 and prod.buffer_count():
                    prod.retry_buffer()
            drv.flush()
            wall = time.perf_counter() - t0
            snap = plane.snapshot() if enabled else None
            ch.close()
            return (round(n / wall, 1) if fed[0] == len(blobs)
                    else float("nan"), snap)
        finally:
            set_attrib(prev)
            shutil.rmtree(shm_dir, ignore_errors=True)

    # untimed warmup (tick-program compile + caches), then best-of-2 per
    # leg: the quick shape's wall is <1s, where a single scheduler
    # hiccup is bigger than the 2% gate being measured
    leg(False, bare_blobs)
    off_rps = max(leg(False, bare_blobs)[0], leg(False, bare_blobs)[0])
    on1, snap = leg(True, carriage_blobs)
    on2, _ = leg(True, carriage_blobs)
    on_rps = max(on1, on2)
    overhead_pct = (off_rps - on_rps) / off_rps * 100.0
    return {
        "records": n,
        "frame_batches": len(bare_blobs),
        "records_per_s_off": off_rps,
        "records_per_s_on": on_rps,
        "overhead_pct": round(overhead_pct, 2),
        "gate_pct": 2.0,
        "within_gate": bool(overhead_pct < 2.0),
        "estimate": snap["estimate"],
        "stages_recorded": sorted(snap["stages"].keys()),
        "occupancy_recorded": sorted(snap["occupancy"].keys()),
    }


def run(quick: bool = False, *, services: int = 100, ticks: int = 64, tx_per_tick: int = 4096) -> dict:
    import jax

    if quick:
        ticks, tx_per_tick = 5, 256

    capacity = 128  # 100 live rows padded to the power-of-two tier
    bare = _measure(ticks, tx_per_tick, services, capacity, telemetry=False)
    teleme = _measure(ticks, tx_per_tick, services, capacity, telemetry=True)
    overhead_pct = (bare["throughput"] - teleme["throughput"]) / bare["throughput"] * 100.0
    delivery = _measure_delivery(quick)
    tracing = _measure_tracing(quick)
    recorder = _measure_recorder(quick)
    transports = _measure_transports(quick)
    attribution = _measure_attribution(quick)

    tick, sched, lat, rebuilds = bare["tick"], bare["sched"], bare["lat"], bare["rebuilds"]
    return result(
        "rolling_baseline_throughput",
        bare["throughput"],
        *per_chip_unit(),
        {
            "config": "BASELINE.json configs[1]",
            "device": str(jax.devices()[0]),
            "services": services,
            "capacity": capacity,
            "ticks": ticks,
            "tx_per_tick": tx_per_tick,
            "tick_latency": latency_stats_ms(lat),
            "executor": tick.kind,
            "rebuild_integrated": bool(tick.rebuild_integrated),
            # integrated rebuild (fused executor): the chunk rides the tick
            # program, so its cost is inside tick_latency — 0.0 here means
            # "charged in the tick", not "not executed"
            "rebuild_ms_per_tick": round(sum(rebuilds) / max(ticks, 1) * 1000, 3),
            "rebuild_native": bool(getattr(sched, "_native", False)),
            "wall_s": round(bare["wall"], 3),
            # ISSUE 2 acceptance: live exporter + per-tick histograms + 2 Hz
            # scraper vs bare loop, same shape same process
            "telemetry": {
                "throughput_on": round(teleme["throughput"], 1),
                "throughput_off": round(bare["throughput"], 1),
                "overhead_pct": round(overhead_pct, 2),
                "scrapes_during_run": teleme["scrapes"],
                "tick_latency_on": latency_stats_ms(teleme["lat"]),
            },
            # ISSUE 3 acceptance: at-least-once epoch checkpoint+ack cadence
            # vs the at-most-once default, same stream same process
            "delivery": delivery,
            # ISSUE 5 acceptance: distributed trace plane at default 1/64
            # head sampling (+ live /trace scraper) vs sampling OFF
            "tracing": tracing,
            # ISSUE 12 acceptance: fleet recorder persisting /metrics +
            # /trace + /decisions to the on-disk store at 2 Hz vs bare loop
            "recorder": recorder,
            # ISSUE 15 acceptance: per-broker throughput (memory vs spool vs
            # fake-redis vs real redis when present) and broker-outage
            # recovery time with zero-loss proof
            "transports": transports,
            # ISSUE 17 acceptance: attribution plane + APC1 carriage ON vs
            # OFF over the frames->shmring->driver loop — the accounting
            # price must stay under the 2% gate
            "attribution": attribution,
        },
    )
