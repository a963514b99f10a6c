"""Shared benchmark plumbing: result schema + percentile helpers.

Baseline anchors (BASELINE.md): the reference publishes no benchmark numbers,
so ``vs_baseline`` compares against the two quantitative anchors that exist —
the north-star target (1M metrics/sec on a v5e-8 => 125k/sec/chip) for device
throughput benches, and the reference's observed operational rates (~76
FullStat records/sec across the prod fleet, 2 JMX hosts per 60 s poll) for the
host-pipeline benches.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np


PER_CHIP_NORTH_STAR = 125_000.0  # metrics/sec/chip (1M / 8 chips)
POD_NORTH_STAR = 1_000_000.0  # metrics/sec, whole pod
REFERENCE_FULLSTAT_RATE = 76.0  # FullStat records/sec in prod (stream_insert_db.js:3-4)
REFERENCE_JMX_HOST_RATE = 2.0 / 60.0  # hosts polled per second (2 hosts / 60 s)


def result(metric: str, value: float, unit: str, baseline: Optional[float], details: Dict) -> Dict:
    return {
        "metric": metric,
        "value": round(float(value), 1),
        "unit": unit,
        "vs_baseline": None if baseline is None else round(float(value) / baseline, 3),
        "details": details,
    }


def per_chip_unit() -> Tuple[str, Optional[float]]:
    """(unit, baseline) of a device-throughput bench: per chip against the
    per-chip north star only when a TPU measured it; otherwise the unit
    names the device and there is no chip baseline to compare with."""
    import jax

    platform = jax.devices()[0].platform
    if platform == "tpu":
        return "metrics/sec/chip", PER_CHIP_NORTH_STAR
    return f"metrics/sec on {platform}", None


def latency_stats_ms(samples_s: List[float]) -> Dict:
    arr = np.asarray(samples_s) * 1000.0
    return {
        "p50_ms": round(float(np.percentile(arr, 50)), 3),
        "p95_ms": round(float(np.percentile(arr, 95)), 3),
        "mean_ms": round(float(arr.mean()), 3),
    }


def timed(fn: Callable[[], None]) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
