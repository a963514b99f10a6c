"""A saved day: the z-score history a configuration's ``history`` section asks for.

The upstream runs every day but its first from the state it saved: each
module reloads its z-score lists from its resume file at boot, full at both
lags, and then catches up the backlog of the outage. A configuration file
may say so:

    "history": {"intervals": 8640, "outage_intervals": 180}

``intervals`` is the length of the saved series (at least the longest
lag); ``outage_intervals`` the gap between its last interval and the first
backlog record's (at least window + buffer, so no bucket of the saved
state could still count). The series is drawn from the seed: for every
engine key of the fleet and every interval, one window row (average, p75,
p95) from that key's distribution under the cell's own traffic, the
records per window the key has in the generated backlog and the elapsed
times of the traffic's ``records.elapsed_ms`` (uniform integers): each
statistic normal about its value for that distribution, with the standard
error of a window of that many records, truncated at ``TRUNCATE_SD``. The
rows are independent draws (a real window shares 30 of its 31 buckets
with the one before; the lists' mean and spread are the same). Values are
rounded to float32, the engine's ring dtype.

No drawn value lies ``TRUNCATE_SD`` standard errors from the mean, far
inside the lags' thresholds, so none could have raised a signal, no
influence applied, and each lag's list is exactly the series' last ``lag``
values; ``generate`` asserts it over every full window of the series.

``write_resume`` writes that state as the engine's resume file, in the
program's own snapshot schema (``PipelineDriver._capture_resume_arrays``):
the registry of the history's keys, ``latest_bucket`` at its last
interval, empty stats buckets, every lag's ring full (``z{lag}_values``
oldest first from slot 0, ``fill`` = lag, the shared cursor ``pos`` 0 at
the oldest), no alert counts, no pending tx rows. The served worker
restores it at boot through its own path. The check seeds its reference's
lists from the same ``History``.
"""

from __future__ import annotations

import math
from collections import Counter
from typing import Dict, Optional, Sequence

import numpy as np

from .fixture import records_params
from .spec import SpecError

TRUNCATE_SD = 4.0
PERCENTILES = (0.75, 0.95)  # per75, per95 (after the average)
SEED_STREAM = 0x4859535431  # the history's own stream of the run's seed


def history_params(cfg_file: dict, program_cfg: dict) -> Optional[dict]:
    """The configuration's ``history`` section, checked against the program
    configuration it runs under; None where it has none."""
    hist = cfg_file.get("history")
    if hist is None:
        return None
    unknown = set(hist) - {"intervals", "outage_intervals"}
    if unknown:
        raise SpecError(f"unknown history parameters {sorted(unknown)}")
    intervals, outage = int(hist["intervals"]), int(hist["outage_intervals"])
    lags = [int(d["LAG"]) for d in program_cfg["streamCalcZScore"]["defaults"]]
    st = program_cfg["streamCalcStats"]
    keep = int(st["windowSizeInIntervals"]) + int(st["bufferSizeInIntervals"])
    eng = program_cfg["tpuEngine"]
    if intervals < max(lags):
        raise SpecError(f"history.intervals {intervals} is shorter than the lag {max(lags)}")
    if outage < keep:
        raise SpecError(f"history.outage_intervals {outage} is under window + buffer ({keep})")
    if eng.get("checkpointMode", "full") != "full":
        raise SpecError("history is written as a full snapshot; "
                        f"tpuEngine.checkpointMode {eng['checkpointMode']!r} restores from a chain")
    if eng.get("ewmaChannels"):
        raise SpecError("history holds no EWMA channel state, and the check compares none")
    return {"intervals": intervals, "outage_intervals": outage}


class History:
    """``series[k, m, t]``: key ``keys[k]``'s window row ``m`` (average,
    per75, per95) at interval ``last_label - T + 1 + t``, float32."""

    def __init__(self, keys: Sequence[tuple], series: np.ndarray, last_label: int):
        self.keys = list(keys)
        self.series = series
        self.last_label = int(last_label)
        self._index = {k: i for i, k in enumerate(self.keys)}

    def lists(self, keys: Sequence[tuple], lag: int) -> np.ndarray:
        """The lists of ``keys`` at one lag, [len(keys), 3, lag], oldest
        first, as float64: the last ``lag`` values of each key's series."""
        missing = [k for k in keys if k not in self._index]
        if missing:
            raise SpecError(f"no history for keys {missing[:3]}")
        idx = [self._index[k] for k in keys]
        return self.series[idx, :, -lag:].astype(np.float64)


def _records_per_window(fleet, keys: Sequence[tuple], interval_ms: int,
                        window_labels: int) -> np.ndarray:
    """Each key's records per window in the generated backlog: its engine
    records per interval of the backlog's span, times the window's intervals."""
    eng = fleet.rec_route == 0
    labels = fleet.rec_end_ms[eng] // interval_ms
    span = int(labels.max() - labels.min() + 1)
    count = Counter(k for k, r in zip(zip(fleet.rec_server, fleet.rec_service),
                                      eng.tolist()) if r)
    return np.asarray([count[k] for k in keys], np.float64) / span * window_labels


def _truncated_normal(rng: np.random.Generator, shape) -> np.ndarray:
    z = rng.standard_normal(shape)
    bad = np.abs(z) > TRUNCATE_SD
    while bad.any():
        z[bad] = rng.standard_normal(int(bad.sum()))
        bad = np.abs(z) > TRUNCATE_SD
    return z


def _assert_no_signal(series: np.ndarray, lags: Sequence[tuple]) -> None:
    """No value of the series lies beyond its lag's threshold from the full
    list before it: the lists hold the series itself, undamped."""
    x = series.astype(np.float64)
    c1 = np.concatenate([np.zeros(x.shape[:-1] + (1,)), np.cumsum(x, -1)], -1)
    c2 = np.concatenate([np.zeros(x.shape[:-1] + (1,)), np.cumsum(x * x, -1)], -1)
    T = x.shape[-1]
    for lag, threshold, _influence in lags:
        if T <= lag:
            continue  # the list fills with the series' last value: no full step
        mean = (c1[..., lag:T] - c1[..., :T - lag]) / lag
        var = (c2[..., lag:T] - c2[..., :T - lag]) / lag - mean * mean
        dev = np.abs(x[..., lag:] - mean)
        # 1% of room for the float64 running sums
        if not (dev < 0.99 * threshold * np.sqrt(np.maximum(var, 0.0))).all():
            raise RuntimeError(f"the generated history would raise a signal at lag {lag}")


def generate(fleet, keys: Sequence[tuple], params: dict, spec, lags: Sequence[tuple],
             records: Optional[dict], seed: int) -> History:
    """The history of ``keys`` for a fleet whose backlog is generated: from
    the seed, ``params['intervals']`` window rows per key, ending
    ``params['outage_intervals']`` before the backlog's first interval."""
    lo, hi = (float(x) for x in records_params(records)["elapsed_ms"])
    width = hi - lo + 1.0  # uniform integers in [lo, hi]
    n = _records_per_window(fleet, keys, spec.interval_ms, len(spec.buckets(0)))
    if (n < 1).any():
        raise SpecError("a key of the history has under one record per window")
    mean = np.empty((len(keys), 3))
    err = np.empty((len(keys), 3))
    mean[:, 0] = (lo + hi) / 2.0
    err[:, 0] = math.sqrt((width * width - 1.0) / 12.0) / np.sqrt(n)
    for m, q in enumerate(PERCENTILES, start=1):
        mean[:, m] = lo + q * (hi - lo)
        err[:, m] = width * math.sqrt(q * (1.0 - q)) / np.sqrt(n)
    T = int(params["intervals"])
    rng = np.random.default_rng([int(seed), SEED_STREAM])
    z = _truncated_normal(rng, (len(keys), 3, T))
    series = (mean[..., None] + err[..., None] * z).astype(np.float32)
    _assert_no_signal(series, lags)
    eng = fleet.rec_route == 0
    first = int((fleet.rec_end_ms[eng] // spec.interval_ms).min())
    return History(keys, series, first - int(params["outage_intervals"]))


def resume_arrays(history: History, program_cfg: dict) -> Dict[str, np.ndarray]:
    """The engine's snapshot of ``history`` (npz schema), every plane at
    ``tpuEngine.serviceCapacity`` rows."""
    eng = program_cfg["tpuEngine"]
    st = program_cfg["streamCalcStats"]
    rows = int(eng["serviceCapacity"])
    n = len(history.keys)
    if n > rows:
        raise SpecError(f"history holds {n} keys, serviceCapacity {rows}")
    dtype = np.float64 if eng.get("dtype") == "float64" else np.float32
    nb = int(st["windowSizeInIntervals"]) + int(st["bufferSizeInIntervals"]) + 1
    cap = int(eng["samplesPerBucket"])
    out = {
        "latest_bucket": np.asarray(history.last_label, np.int32),
        "counts": np.zeros((rows, nb), np.int32),
        "sums": np.zeros((rows, nb), dtype),
        "samples": np.full((rows, nb, cap), np.nan, dtype),
        "nsamples": np.zeros((rows, nb), np.int32),
    }
    for d in program_cfg["streamCalcZScore"]["defaults"]:
        lag = int(d["LAG"])
        values = np.full((rows, 3, lag), np.nan, np.float32)
        values[:n] = history.series[:, :, -lag:]
        fill = np.zeros(rows, np.int32)
        fill[:n] = lag
        out[f"z{lag}_values"] = values
        out[f"z{lag}_fill"] = fill
        out[f"z{lag}_pos"] = np.zeros((), np.int32)  # the next push evicts slot 0, the oldest
        out[f"z{lag}_counters"] = np.zeros(rows, np.int32)
    out["registry"] = np.array(["\x00".join(k) for k in history.keys], dtype=object)
    out["pending_tx"] = np.array([], dtype=object)
    return out


def write_resume(path: str, history: History, program_cfg: dict) -> None:
    """The engine's resume file at ``path`` (uncompressed: written and read
    once per run, and random floats do not compress)."""
    with open(path, "wb") as fh:
        np.savez(fh, **resume_arrays(history, program_cfg))

