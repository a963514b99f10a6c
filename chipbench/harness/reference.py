"""The plain float64 reference of the engine's statistics, and its control.

Copied from ``tests/golden.py`` (``GoldenStats``, ``GoldenZScore``) and
``apmbackend_tpu/utils/jsmath.py`` (the reference's util_methods.js helpers,
quirks included) so that it imports nothing of the program. It is fed the
generator's records, never what the device ingested.

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
