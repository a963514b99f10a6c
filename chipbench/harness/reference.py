"""The plain float64 reference of the engine's statistics, and its control.

Copied from ``tests/golden.py`` (``GoldenStats``, ``GoldenZScore``),
``apmbackend_tpu/utils/jsmath.py`` (the reference's util_methods.js helpers,
quirks included) and ``apmbackend_tpu/entries.py`` (``js_to_fixed``) so that
it imports nothing of the program. It is fed the generator's records, never
what the device ingested.

``ZScoreLists`` is ``GoldenZScore`` stepped for every key of one lag at
once in numpy, the check's z-score reference; ``GoldenZScore`` stays as its
test oracle. Both take each row's statistics as the wire carries them to
the upstream's z-score process (``js_to_fixed``, one decimal).

``window_row`` is ``GoldenStats.generate_all`` for one key at one tick,
over every record of the buckets the tick's window covers: the row the
reference emits when all of those records have arrived before the tick.
The replay and the tailer deliver each bucket well inside the six buffer
intervals, which is what makes that row the right one; a record that came
later than that would show as a count gap.

``control_row`` is the same computation with the elapsed values, the sum
and the results held in bfloat16: the next precision below the float32 the
engine states (``tpuEngine.dtype``). Its gaps to the float64 row are what a
lower-precision engine would give.
"""

from __future__ import annotations

import math
from decimal import ROUND_HALF_DOWN, ROUND_HALF_UP, Decimal
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np

NAN = float("nan")


# ----------------------------------------------------------- jsmath copy ----

def _valid(x) -> bool:
    if x is None:
        return False
    try:
        return not math.isnan(float(x))
    except (TypeError, ValueError):
        return False


def js_average(values: Iterable) -> Optional[float]:
    cnt = 0
    total = 0.0
    for v in values:
        if _valid(v):
            cnt += 1
            total += float(v)
    return total / cnt if cnt > 0 else None


def js_standard_deviation(values: Sequence) -> Optional[float]:
    avg = js_average(values)
    if avg is None:
        return None
    sq = [((float(v) - avg) ** 2) if _valid(v) else None for v in values]
    avg_sq = js_average(sq)
    if avg_sq:  # a zero variance has no standard deviation (util_methods.js:44-48)
        return math.sqrt(avg_sq)
    return None


def js_percentile(sorted_values: Sequence[float], percentile: float) -> Optional[float]:
    """util_methods.js:112-142: index = p/100*n - 1; an integer index picks
    that element, otherwise the mean of arr[ceil] and arr[ceil+1] unless
    ceil is the last element."""
    n = len(sorted_values)
    if n == 0:
        return None
    if percentile == 0:
        return sorted_values[0]
    if percentile == 100:
        return sorted_values[-1]
    index = (percentile / 100.0) * n - 1.0
    if n == 1 or index == int(index):
        return sorted_values[int(index)]
    index = int(math.ceil(index))
    if index == n - 1:
        return sorted_values[index]
    return (sorted_values[index] + sorted_values[index + 1]) / 2.0


def js_to_fixed(x: float, digits: int = 1) -> float:
    """``parseFloat(x.toFixed(digits))``: the nearest decimal to the exact
    binary value, an exact tie toward +inf (Number.prototype.toFixed), as the
    stats reach the z-score over the wire (StatEntry.toCSVString, entries.js:72).
    NaN stays NaN."""
    if math.isnan(x):
        return NAN
    q = Decimal(1).scaleb(-digits)
    return float(Decimal(x).quantize(q, rounding=ROUND_HALF_UP if x >= 0 else ROUND_HALF_DOWN))


# how near a rounding tie a float32 engine's value may fall on either side:
# a few float32 epsilons of the value
TIE_REACH = 4 * 2.0 ** -23


def wire_tie(x: float, digits: int = 1) -> bool:
    """Whether ``x`` lies at a rounding tie of ``js_to_fixed``, or within a
    float32 engine's reach of one: there the engine may round either way."""
    if math.isnan(x):
        return False
    t = abs(x) * 10.0 ** digits
    return abs(t - math.floor(t) - 0.5) <= TIE_REACH * t


# ------------------------------------------------------ GoldenStats copy ----

class WindowSpec:
    """windowSizeInIntervals / bufferSizeInIntervals / intervalLengthInSeconds."""

    def __init__(self, window_sz: int = 30, buffer_sz: int = 6, interval_s: int = 10):
        self.window_sz = window_sz
        self.buffer_sz = buffer_sz
        self.interval_ms = interval_s * 1000
        self.num_keep = window_sz + buffer_sz
        self.tpm_div = window_sz * interval_s / 60.0

    def tick_label(self, edge_ts_ms: int) -> int:
        """The bucket whose first record fired the tick that emitted a row
        stamped ``edge_ts_ms`` (edge = (latest - buffer - 1) * interval)."""
        return edge_ts_ms // self.interval_ms + self.buffer_sz + 1

    def buckets(self, latest: int) -> range:
        """Buckets in the row of the tick at ``latest``."""
        return range(latest - self.num_keep, latest - self.buffer_sz + 1)


def window_row(spec: WindowSpec, buckets: Dict[int, List[int]], latest: int) -> dict:
    """One key's row at the tick that opened ``latest``: GoldenStats'
    generate_all over that key's buckets."""
    vals: List[int] = []
    for label in spec.buckets(latest):
        vals.extend(buckets.get(label, ()))
    cnt = len(vals)
    avg = p75 = p95 = None
    if cnt:
        vals.sort()
        avg = float(sum(vals)) / cnt
        p75 = js_percentile(vals, 75)
        p95 = js_percentile(vals, 95)
    return {"count": cnt, "tpm": cnt / spec.tpm_div,
            "average": NAN if avg is None else avg,
            "per75": NAN if p75 is None else p75,
            "per95": NAN if p95 is None else p95}


def _bf16(x: float) -> float:
    """Round to the nearest bfloat16 (ties to even)."""
    import ml_dtypes

    return float(np.asarray(x, np.float64).astype(ml_dtypes.bfloat16))


def control_row(spec: WindowSpec, buckets: Dict[int, List[int]], latest: int) -> dict:
    """``window_row`` with elapsed values, the running sum and the results
    held in bfloat16."""
    vals: List[float] = []
    for label in spec.buckets(latest):
        vals.extend(_bf16(v) for v in buckets.get(label, ()))
    cnt = len(vals)
    avg = p75 = p95 = None
    if cnt:
        vals.sort()
        total = 0.0
        for v in vals:
            total = _bf16(total + v)
        avg = _bf16(total / cnt)
        p75 = _bf16(js_percentile(vals, 75))
        p95 = _bf16(js_percentile(vals, 95))
    return {"count": cnt, "tpm": _bf16(cnt / spec.tpm_div),
            "average": NAN if avg is None else avg,
            "per75": NAN if p75 is None else p75,
            "per95": NAN if p95 is None else p95}


# ----------------------------------------------------- GoldenZScore copy ----

class GoldenZScore:
    """Per-key rolling lists with influence damping (stream_calc_z_score.js)."""

    def __init__(self, lag: int, threshold: float, influence: float):
        self.lag = lag
        self.threshold = threshold
        self.influence = influence
        self.lists: Dict[tuple, Dict[str, List[float]]] = {}

    def _process_metric(self, new_value: float, lst: List[float]):
        infl_new = new_value
        avg = std = lb = ub = None
        signal = 0
        if len(lst) >= self.lag:
            avg = js_average(lst)
            std = js_standard_deviation(lst)
            vals = [v for v in lst if v is not None and not math.isnan(v)]
            if vals and min(vals) == max(vals):
                avg = vals[0]
                std = None
            if (avg is not None) and (std is not None):
                lb = avg - self.threshold * std
                ub = avg + self.threshold * std
            if avg is None or std is None:
                signal = 0
            elif math.isnan(new_value):
                signal = 0
            elif abs(new_value - avg) > self.threshold * std:
                signal = 1 if new_value > avg else -1
                last = lst[-1] if lst else None
                if last is not None and not math.isnan(last):
                    infl_new = self.influence * new_value + (1 - self.influence) * last
        nan = lambda v: NAN if v is None else v  # noqa: E731
        return infl_new, nan(avg), nan(lb), nan(ub), signal

    def step(self, key: tuple, average: float, per75: float, per95: float) -> dict:
        lists = self.lists.setdefault(key, {"average": [], "per75": [], "per95": []})
        out = {}
        for metric, val in (("average", average), ("per75", per75), ("per95", per95)):
            lst = lists[metric]
            infl, avg, lb, ub, sig = self._process_metric(val, lst)
            if len(lst) >= self.lag:
                lst.pop(0)
            lst.append(infl)
            out[metric] = {"avg": avg, "lb": lb, "ub": ub, "signal": sig}
        return out


class ZScoreLists:
    """``GoldenZScore`` of one lag stepped for many keys at once, in float64.

    Row ``k`` holds key ``k``'s three lists (average, per75, per95) as a
    ring: ``values[k, m, :]``, ``length[k]`` values pushed so far (at most
    ``lag``) and ``pos[k]`` the slot the next push takes, which is the
    oldest value once the list is full. Every quirk of ``_process_metric``
    is kept: no statistics until ``length >= lag`` (NaN pushes count), NaN
    entries skipped, a zero variance or all-equal valid entries give no
    standard deviation (and the mean is then that value), a NaN new value
    raises no signal, influence mixes in the list's last value unless it is
    NaN, and the (damped) value is pushed, the oldest dropped, after the
    statistics. Mean and variance are taken over the whole list at every
    step, as the upstream takes them; only the order of the float64 sums
    differs from ``GoldenZScore``'s.

    ``start[k, m, :]`` (optional) are key ``k``'s lists before the first
    step, oldest first, at most ``lag`` long.

    Each entry may carry an uncertainty ``unc``: how far an engine's value
    may lie from it (a wire rounding tie, ``wire_tie``). ``step`` returns a
    ``slack`` that bounds what those do to avg and to thr x std: at most
    sum(unc) / n and thr x sqrt(sum(unc^2) / n) over the n valid entries (the
    standard deviation is a norm of the centred list)."""

    def __init__(self, n_keys: int, lag: int, threshold: float, influence: float,
                 start: Optional[np.ndarray] = None):
        self.lag = int(lag)
        self.threshold = float(threshold)
        self.influence = float(influence)
        self.values = np.full((n_keys, 3, self.lag), np.nan)
        self.length = np.zeros(n_keys, np.int64)
        if start is not None:
            start = np.asarray(start, np.float64)
            m = start.shape[-1]
            if start.shape != (n_keys, 3, m) or m > self.lag:
                raise ValueError(f"start lists of shape {start.shape} for {n_keys} keys, "
                                 f"lag {self.lag}")
            self.values[:, :, :m] = start
            self.length[:] = m
        self.pos = self.length % self.lag
        self.unc = np.zeros((n_keys, 3, self.lag))  # pages untouched until a tie
        self._unc_n = np.zeros((n_keys, 3), np.int64)  # entries with some uncertainty
        self._unc_sum = np.zeros((n_keys, 3))
        self._unc_sq = np.zeros((n_keys, 3))
        self._dev = None

    def _stats(self, rows: np.ndarray):
        """(avg, std, valid entries) of ``rows``' lists, [len(rows), 3]; NaN
        and 0 where the list is not full."""
        n = len(rows)
        avg = np.full((n, 3), np.nan)
        std = np.full((n, 3), np.nan)
        valid_n = np.zeros((n, 3))
        full = np.nonzero(self.length[rows] >= self.lag)[0]
        if len(full) == 0:
            return avg, std, valid_n
        sel = rows[full]
        vals = self.values if len(sel) == len(self.values) and np.array_equal(
            sel, np.arange(len(self.values))) else self.values[sel]
        if self._dev is None or self._dev.shape != vals.shape:
            self._dev = np.empty_like(vals)  # reused: a fresh buffer per step costs its page faults
        valid = ~np.isnan(vals)
        where = None if valid.all() else valid
        cnt = np.full(vals.shape[:-1], self.lag) if where is None else valid.sum(-1)
        with np.errstate(invalid="ignore", divide="ignore"):
            mean = np.add.reduce(vals, axis=-1, where=True if where is None else where) / cnt
            dev = np.subtract(vals, mean[..., None], out=self._dev)
            np.square(dev, out=dev)
            var = np.add.reduce(dev, axis=-1, where=True if where is None else where) / cnt
        lo = np.fmin.reduce(vals, axis=-1)  # fmin and fmax pass over NaN
        hi = np.fmax.reduce(vals, axis=-1)
        same = (cnt > 0) & (lo == hi)
        sd = np.where(var > 0, np.sqrt(np.where(var > 0, var, 0.0)), np.nan)
        avg[full] = np.where(same, lo, mean)
        std[full] = np.where(same, np.nan, sd)
        valid_n[full] = cnt
        return avg, std, valid_n

    def step(self, rows: Sequence[int], new: np.ndarray, unc: Optional[np.ndarray] = None):
        """One step of the keys ``rows`` with their new values ``new`` and
        those values' uncertainties ``unc`` (0 where left out), each
        [len(rows), 3]: (avg, lb, ub, signal, slack), each [len(rows), 3]."""
        rows = np.asarray(rows, np.int64)
        new = np.asarray(new, np.float64).reshape(len(rows), 3)
        unc = np.zeros_like(new) if unc is None else np.asarray(unc, np.float64).reshape(new.shape)
        unc = np.where(np.isnan(new), 0.0, unc)
        avg, std, valid_n = self._stats(rows)
        n = np.maximum(valid_n, 1)
        slack = np.where(valid_n > 0, self._unc_sum[rows] / n
                         + self.threshold * np.sqrt(self._unc_sq[rows] / n), 0.0)
        lb = avg - self.threshold * std
        ub = avg + self.threshold * std
        with np.errstate(invalid="ignore"):
            dev = new - avg
            fired = np.abs(dev) > self.threshold * std  # False wherever a NaN is in it
        signal = np.where(fired, np.where(dev > 0, 1, -1), 0)
        pos = self.pos[rows]
        last = self.values[rows, :, (pos - 1) % self.lag]
        has_last = (self.length[rows] > 0)[:, None] & ~np.isnan(last)
        damped = fired & has_last
        pushed = np.where(damped, self.influence * new + (1 - self.influence) * last, new)
        last_unc = self.unc[rows, :, (pos - 1) % self.lag]
        pushed_unc = np.where(damped, self.influence * unc + (1 - self.influence) * last_unc, unc)
        gone = self.unc[rows, :, pos] * (self.length[rows] >= self.lag)[:, None]
        left = self._unc_n[rows] + (pushed_unc > 0) - (gone > 0)
        self._unc_n[rows] = left
        self._unc_sum[rows] = np.where(left > 0, self._unc_sum[rows] + pushed_unc - gone, 0.0)
        self._unc_sq[rows] = np.where(left > 0, self._unc_sq[rows] + pushed_unc ** 2 - gone ** 2,
                                      0.0)
        self.values[rows, :, pos] = pushed
        self.unc[rows, :, pos] = pushed_unc
        self.pos[rows] = (pos + 1) % self.lag
        self.length[rows] = np.minimum(self.length[rows] + 1, self.lag)
        return avg, lb, ub, signal, slack
