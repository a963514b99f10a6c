"""BASELINE.json configs[4]: multi-window seasonal/EWMA baselining + on-device
alert threshold eval.

The engine with BOTH fixed-lag z-score windows (1 h + 24 h) and the O(1)
EWMA-family channels (plain EWMA + 24-slot hour-of-day seasonal + Holt
level-and-trend), each with the full alert rule ladder (hard thresholds,
both-only gate, rolling bad-interval counters) evaluated on device. Reports
metrics/sec/chip across all five channels against the per-chip north star.
"""

from __future__ import annotations

import time

import numpy as np

from .common import latency_stats_ms, per_chip_unit, result

EWMA_CHANNELS = [
    {"ALPHA": 0.05, "THRESHOLD": 3.0, "WARMUP": 30, "CHANNEL_ID": -1},
    {"ALPHA": 0.2, "THRESHOLD": 3.0, "WARMUP": 3, "SEASON_SLOTS": 24,
     "SLOT_INTERVALS": 360, "CHANNEL_ID": -24},
    # Holt level+trend channel: baselines ramping services against the
    # extrapolated slope (ops/ewma.py trend_beta)
    {"ALPHA": 0.1, "THRESHOLD": 3.0, "WARMUP": 30, "CHANNEL_ID": -2,
     "TREND_BETA": 0.2},
]


def run(quick: bool = False, *, capacity: int = 8192, ticks: int = 64, tx_per_tick: int = 16384) -> dict:
    import jax

    from apmbackend_tpu.pipeline import (
        RebuildScheduler,
        engine_ingest,
        make_demo_engine,
        make_engine_step,
    )

    if quick:
        capacity, ticks, tx_per_tick = 64, 4, 512

    lags = [(4, 20.0, 0.1), (8, 15.0, 0.0)] if quick else [(360, 20.0, 0.1), (8640, 15.0, 0.0)]
    cfg, state, params = make_demo_engine(
        capacity, 32 if quick else 64, lags, ewma_channels=EWMA_CHANNELS
    )
    # staged executor: in-place big-buffer writes (pipeline.make_engine_step)
    tick = make_engine_step(cfg)
    ingest = jax.jit(engine_ingest, static_argnums=1, donate_argnums=(0,))
    # staggered rebuild executed + charged in the measured loop (r4 VERDICT)
    sched = None if tick.rebuild_integrated else RebuildScheduler(cfg)

    rng = np.random.RandomState(0)
    label = 170_000_000

    def batch(lbl):
        rows = rng.randint(0, capacity, tx_per_tick).astype(np.int32)
        labels = np.full(tx_per_tick, lbl, np.int32)
        elaps = (200 + 50 * rng.rand(tx_per_tick)).astype(np.float32)
        return rows, labels, elaps, np.ones(tx_per_tick, bool)

    for _ in range(3):
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
        _ = [np.asarray(l.trigger) for l in em.lags + em.ewma]
        lat.append(time.perf_counter() - t0)
        tr = time.perf_counter()
        if sched is not None:
            state = sched.step_synced(state)
        rebuilds.append(time.perf_counter() - tr)
        state = ingest(state, cfg, *batch(label))
    jax.block_until_ready(state.stats.counts)
    wall = time.perf_counter() - t_start

    n_channels = len(cfg.lags) + len(cfg.ewma)
    metrics_per_tick = capacity * 3 * n_channels
    throughput = metrics_per_tick * ticks / (sum(lat) + sum(rebuilds))
    return result(
        "multiwindow_baselining_throughput",
        throughput,
        *per_chip_unit(),
        {
            "config": "BASELINE.json configs[4]",
            "device": str(jax.devices()[0]),
            "capacity": capacity,
            "channels": {
                "lags": [spec.lag for spec in cfg.lags],
                "ewma": [spec.channel_id for spec in cfg.ewma],
            },
            "ticks": ticks,
            "tick_latency": latency_stats_ms(lat),
            "rebuild_ms_per_tick": round(sum(rebuilds) / max(ticks, 1) * 1000, 3),
            "rebuild_native": bool(getattr(sched, "_native", False)),
            "wall_s": round(wall, 3),
        },
    )
