"""The comparison that decides ``correct``.

What the served path committed to the sink's database, after the window
has closed and the pipeline has drained, against the generator's records
and the float64 reference fed those records:

- ``lost_records`` / ``extra_records``: every record of the fed prefix is
  a row of the sink's ``tx`` table or a line of the engine's saved tx
  backlog (records past the last tick's edge wait there), as a multiset of
  (server, service, elapsed). Accounting copied from chip_smoke.py
  ``check_tx_rows``. Intake drops and producer evictions add to
  ``lost_records`` too.
- The reference is ``GoldenStats`` fed the engine-bound records in the
  order the parser hands them on (``reference_rows``): a tick at each
  record whose bucket is newer than any before it, a row for each sampled
  key seen so far. Records the parser parks arrive at a cut that never
  falls from tick to tick and is due ``PARK_DUE_S`` after the parking.
- ``missing_rows``: reference rows of sampled keys with no committed row
  (per lag), committed rows with no reference row, and duplicates.
- ``count_gap``: the widest gap between a committed row's count
  (tpm x window minutes) and the reference's.
- ``null_mismatch``: values that are null on one side only.
- ``stat_gap``: the widest gap, in ms, between a committed average / p75 /
  p95 and the reference's, both at the wire's rounding (one decimal); on
  an exact rounding tie either neighbour counts (chip_smoke.py
  ``_wire_equal``).
- ``zscore_mismatch``: z-score bands (avg, lb, ub) and signals of both
  lags against ``ZScoreLists`` (``GoldenZScore`` stepped for every key at
  once, in float64) fed the reference's rows as the wire rounds them
  (``js_to_fixed``) at the same ticks. A band is off by more than the
  wire's half digit plus ``BAND_RTOL`` of |avg| + thr x std, or a signal
  differs, outside the slack of the wire's rounding ties in the list (a
  float32 engine may round a tie the other way; the stats' own rule).
- ``alerts_unexpected``: alert rows of sampled keys whose reference raised
  no signal.
- ``rows_compared``: at least one row with transactions was compared.
- ``bands_compared``: rows in which both sides hold avg, lb and ub for some
  metric; at least 1 where the configuration has a ``history``, else
  printed against 0.

With a ``history`` (``harness/history.py``) the program restores a saved
day at boot: every key of it is registered from the first tick, and each
lag's lists start as the last ``lag`` values of the same series, so bands
and signals are compared from the first tick. Without one, the upstream's
lags (360, 8640) never fill in a run and both sides read no bands.

Not covered: the alert rules' own verdicts. An alert needs
``requiredNumberBadIntervalsInAlertWindowToTrigger`` (45) bad intervals in
its window, more than a run's backlog spans, so only alerts that should
not be there are counted.
"""

from __future__ import annotations

import json
import math
import sqlite3
from collections import Counter, defaultdict
from datetime import datetime
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .reference import WindowSpec, ZScoreLists, js_to_fixed, window_row, wire_tie


def wire_gap(got: Optional[float], want: float, digits: int) -> Optional[float]:
    """Gap between a wire value and the float64 value rounded as the wire
    rounds it; None where exactly one side is null."""
    if want is None or math.isnan(want):
        return 0.0 if got is None else None
    if got is None:
        return None
    scale = 10.0 ** digits
    up = math.floor(want * scale + 0.5) / scale
    cands = [up]
    if abs(want * scale - math.floor(want * scale) - 0.5) < 1e-6:
        cands.append(up - 1.0 / scale)
    return min(abs(float(got) - c) for c in cands)


def iso_ms(stamp: str) -> int:
    return int(round(datetime.fromisoformat(stamp.replace("Z", "+00:00")).timestamp() * 1000))


class Expected:
    """The records of the fed prefix, as the generator wrote them: ``rank``
    is each record's place in the order the parser hands records on (or
    parks them), ``parked`` marks those the parser parks, ``fed`` is the
    feed sequence number of the chunk that completed each."""

    def __init__(self, server: Sequence[str], service: Sequence[str], route: np.ndarray,
                 end_ms: np.ndarray, elapsed: np.ndarray, rank: np.ndarray, parked: np.ndarray,
                 fed: np.ndarray):
        self.server = list(server)
        self.service = list(service)
        self.route = np.asarray(route)
        self.end_ms = np.asarray(end_ms, np.int64)
        self.elapsed = np.asarray(elapsed, np.int64)
        self.rank = np.asarray(rank, np.int64)
        self.parked = np.asarray(parked, bool)
        self.fed = np.asarray(fed, np.int64)

    def __len__(self) -> int:
        return len(self.server)

    def multiset(self) -> Counter:
        return Counter(zip(self.server, self.service, self.elapsed.tolist()))


def sample_keys(keys: Sequence[tuple], n: int, seed: int) -> List[tuple]:
    """A seeded sample of ``n`` keys (all of them where there are fewer)."""
    keys = sorted(keys)
    if len(keys) <= n:
        return keys
    rng = np.random.default_rng(seed)
    return [keys[i] for i in sorted(rng.choice(len(keys), size=n, replace=False).tolist())]


def read_stat_rows(db_path: str, keys: Sequence[tuple]) -> List[tuple]:
    """Committed FullStat rows of ``keys``: (key, edge ts ms, lag, tpm, stats dict)."""
    want = set(keys)
    out = []
    con = sqlite3.connect(db_path, timeout=30)
    try:
        try:
            cur = con.execute("SELECT server, service, timestamp, lag, tpm, stats FROM stats")
        except sqlite3.OperationalError:
            return []
        for server, service, stamp, lag, tpm, blob in cur:
            if (server, service) in want:
                out.append(((server, service), iso_ms(stamp), int(lag),
                            None if tpm is None else float(tpm), json.loads(blob)))
    finally:
        con.close()
    return out


def read_sunk(db_path: str, pending_lines: Sequence[str]) -> Tuple[Counter, Counter, int]:
    """(tx rows, pending backlog lines, alert rows) of the sink."""
    con = sqlite3.connect(db_path, timeout=30)
    try:
        try:
            sunk = Counter((sv, svc, int(el)) for sv, svc, el in
                           con.execute("SELECT server, service, elapsed FROM tx"))
        except sqlite3.OperationalError:
            sunk = Counter()
        try:
            alerts = Counter((sv, svc) for sv, svc in
                             con.execute("SELECT server, service FROM alerts"))
        except sqlite3.OperationalError:
            alerts = Counter()
    finally:
        con.close()
    pending = Counter()
    for line in pending_lines:
        p = line.split("|")
        pending[(p[1], p[2], int(float(p[7])))] += 1
    return sunk, pending, alerts


StatRow = Tuple[tuple, int, int, Optional[float], dict]

AT_LEAST = ("rows_compared", "bands_compared")  # readings held to a floor, not a ceiling

# z-score avg / lb / ub: the wire's half digit plus this share of |avg| + thr x std
BAND_RTOL = 1e-4
WIRE_STEP = 0.1  # what a wire rounding tie may move a value by (one decimal)


# A parked record leaves the parser when its entry in the need-number cache
# expires: 30 s after it was parked (the upstream's TTL, stream_parse_
# transactions.js:213-218), at the next 1 s sweep. Any tick whose record was
# fed this long after a parked record's chunk has that record: 30 s, the
# sweep, and 4 s for the sweep's output to reach the device.
PARK_DUE_S = 35.0


def _cut_counts(parked_k: List[tuple], window: range, before: int) -> List[int]:
    """Ranks of a key's parked records in ``window`` that were parked
    before rank ``before``, ascending."""
    return [r for r, label, _e in parked_k if r < before and label in window]


def reference_rows(expected: "Expected", keys: Sequence[tuple], spec: WindowSpec,
                   device_counts: Dict[tuple, int], fed_times: Sequence[float],
                   row_fn=window_row, registered: Sequence[tuple] = ()):
    """GoldenStats fed the engine-bound records in the order the parser
    hands them on, for the sampled ``keys``.

    Records that leave the parser at once (SOAP/EJB) arrive in their feed
    order, and ticks fire at each of them whose bucket is newer than any
    before (records parked by the parser are never that new). Parked
    records arrive in the order they were parked, once a sweep, on the wall
    clock, lets them go: at each tick some prefix of them has arrived. That
    prefix, the cut, is found from the committed rows' counts, one cut for
    all keys at a tick. The cuts never fall from one tick to the next, and
    each takes in every record parked ``PARK_DUE_S`` or more before the
    tick's record was fed (``fed_times`` maps a feed sequence number to its
    monotonic time). ``cut_gap`` is the widest count gap at the best cut
    within those bounds, 0 when one cut fits every key. Keys in
    ``registered`` (a restored registry) have a row at every tick, records
    or not.

    Returns ({(key, edge): row}, the edges in tick order, cut_gap, the
    number of rows whose key the reference has not seen by that tick)."""
    want = set(keys)
    times = np.asarray(fed_times, np.float64)
    labels = (expected.end_ms // spec.interval_ms).tolist()
    eng = expected.route == 0
    by_rank = np.argsort(expected.rank)
    ontime = [i for i in by_rank.tolist() if eng[i] and not expected.parked[i]]
    all_parked = [i for i in by_rank.tolist() if eng[i] and expected.parked[i]]
    parked_rank = np.asarray([int(expected.rank[i]) for i in all_parked], np.int64)
    parked_time = times[expected.fed[all_parked]] if all_parked else np.zeros(0)
    parked: Dict[tuple, List[tuple]] = defaultdict(list)
    for i in all_parked:
        key = (expected.server[i], expected.service[i])
        if key in want:
            parked[key].append((int(expected.rank[i]), labels[i], int(expected.elapsed[i])))
    seen: Dict[tuple, Dict[int, List[int]]] = defaultdict(lambda: defaultdict(list))
    for key in want.intersection(registered):
        seen[key] = defaultdict(list)
    rows: Dict[tuple, dict] = {}
    edges: List[int] = []
    cut_gap = 0
    unseen = 0
    latest = None
    prev_cut = 0

    def tick(latest: int, before: int, fed_at: float) -> None:
        nonlocal cut_gap, unseen, prev_cut
        edge = (latest - spec.buffer_sz - 1) * spec.interval_ms
        edges.append(edge)
        window = spec.buckets(latest)
        due = int(np.searchsorted(parked_time, fed_at - PARK_DUE_S, side="right"))
        floor = max(prev_cut - 1, int(parked_rank[due - 1]) if due else -1)
        cands, lo, hi, need = {}, floor, before, {}
        for key in keys:
            on = sum(len(seen[key].get(b, ())) for b in window) if key in seen else 0
            first = parked[key][0][0] if parked.get(key) else None
            cands[key] = _cut_counts(parked.get(key, ()), window, before)
            got = device_counts.get((key, edge))
            if got is None:
                if key not in seen and first is not None:
                    hi = min(hi, first)  # not yet registered: nothing of it arrived
                continue
            if key not in seen:
                if first is None or first >= before:
                    unseen += 1
                    continue
                lo = max(lo, first)  # registered by a parked record
            n = got - on
            need[key] = n
            r = cands[key]
            if 0 <= n <= len(r):
                lo = max(lo, r[n - 1] if n else -1)
                hi = min(hi, r[n] if n < len(r) else before)
            else:
                lo, hi = before, -1
        if lo < hi:
            cut = lo + 1
        else:  # no single cut fits: take the one within the bounds that fits best
            best = None
            for c in sorted({r + 1 for v in cands.values() for r in v} | {floor + 1, before}):
                if c <= floor:
                    continue
                gap = max((abs(n - sum(1 for r in cands[k] if r < c)) for k, n in need.items()),
                          default=0)
                if best is None or gap < best[0]:
                    best = (gap, c)
            cut_gap = max(cut_gap, best[0])
            cut = best[1]
        prev_cut = cut
        for key in keys:
            arrived = [e for r, label, e in parked.get(key, ()) if r < cut]
            if key not in seen and not arrived:
                continue
            buckets = defaultdict(list, {b: list(v) for b, v in seen.get(key, {}).items()})
            for r, label, e in parked.get(key, ()):
                if r < cut:
                    buckets[label].append(e)
            rows[(key, edge)] = row_fn(spec, buckets, latest)

    for i in ontime:
        label = labels[i]
        if latest is None or label > latest:
            latest = label
            tick(latest, int(expected.rank[i]), float(times[expected.fed[i]]))
        key = (expected.server[i], expected.service[i])
        if key in want:
            seen[key][label].append(int(expected.elapsed[i]))
    return rows, edges, cut_gap, unseen


def compare_rows(rows: Sequence[StatRow], ref: Dict[tuple, dict], edges: Sequence[int],
                 keys: Sequence[tuple], lags: Sequence[Tuple[int, float, float]],
                 tpm_div: float, start: Optional[Dict[int, np.ndarray]] = None) -> dict:
    """Readings of the committed FullStat rows of ``keys`` against the
    reference's rows: missing_rows (either side), count_gap, null_mismatch,
    stat_gap, zscore_mismatch, bands_compared, rows_compared, and the
    reference's signals. Each lag's z-score lists start as ``start[lag]``
    ([len(keys), 3, n], oldest first) where given, else empty."""
    got: Dict[tuple, StatRow] = {}
    extra = 0
    for row in rows:
        k = (row[0], row[1], row[2])
        if k in got:
            extra += 1
        got[k] = row
    lag_ids = [lag for lag, _t, _i in lags]
    res = {"missing_rows": extra, "count_gap": 0, "null_mismatch": 0, "stat_gap": 0.0,
           "zscore_mismatch": 0, "bands_compared": 0, "rows_compared": 0,
           "signals": Counter()}
    ref_keys = {(key, edge, lag) for (key, edge) in ref for lag in lag_ids}
    res["missing_rows"] += sum(1 for k in got if k not in ref_keys)
    metrics = ("average", "per75", "per95")
    for (lag, thr, infl) in lags:
        z = ZScoreLists(len(keys), lag, thr, infl, None if start is None else start[lag])
        for edge in edges:
            idx = [i for i, key in enumerate(keys) if (key, edge) in ref]
            if not idx:
                continue  # no key had a record yet at this tick
            wants = [ref[(keys[i], edge)] for i in idx]
            news = [[js_to_fixed(w[m], 1) for m in metrics] for w in wants]
            ties = [[WIRE_STEP if wire_tie(w[m], 1) else 0.0 for m in metrics] for w in wants]
            zavg, zlb, zub, zsig, zslack = z.step(idx, news, ties)
            for j, (i, want) in enumerate(zip(idx, wants)):
                key = keys[i]
                row = got.get((key, edge, lag))
                if row is None:
                    res["missing_rows"] += 1
                    continue
                _k, _ts, _lag, tpm, st = row
                if tpm is None:
                    res["null_mismatch"] += 1
                else:
                    res["count_gap"] = max(res["count_gap"],
                                           abs(int(round(tpm * tpm_div)) - want["count"]))
                for name in metrics:
                    gap = wire_gap(st.get(name), want[name], 1)
                    if gap is None:
                        res["null_mismatch"] += 1
                    else:
                        res["stat_gap"] = max(res["stat_gap"], gap)
                banded = False
                for m, name in enumerate(metrics):
                    signal = int(zsig[j, m])
                    if signal:
                        res["signals"][key] += 1
                    # a bound's float32 error grows with its terms, avg and thr x std;
                    # a tie pushed into the list may have gone the other way
                    spread = float(zub[j, m] - zavg[j, m])
                    scale = abs(float(zavg[j, m])) + (0.0 if math.isnan(spread) else spread)
                    room = BAND_RTOL * scale + float(zslack[j, m])
                    if (st.get(name + "signal") or 0) != signal:
                        # only a value within that room of thr x std may fire on one side
                        margin = abs(abs(news[j][m] - float(zavg[j, m])) - spread)
                        if not margin <= room + ties[j][m]:
                            res["zscore_mismatch"] += 1
                    both = True
                    for part, arr in (("avg", zavg), ("lb", zlb), ("ub", zub)):
                        want_part = float(arr[j, m])
                        gap = wire_gap(st.get(name + part), want_part, 1)
                        if gap is None or gap > 0.05 + room:
                            res["zscore_mismatch"] += 1
                        both = (both and st.get(name + part) is not None
                                and not math.isnan(want_part))
                    banded = banded or both
                res["bands_compared"] += banded
                if want["count"]:
                    res["rows_compared"] += 1
    return res


def judge(expected: Expected, db_path: str, pending_lines: Sequence[str], keys: Sequence[tuple],
          spec: WindowSpec, lags, *, fed_times: Sequence[float], dropped: int,
          limits: Dict[str, float], control=None, history=None) -> dict:
    """Every reading with its limit: {name: {"value", "limit", "ok"}}, plus
    ``_failed`` (records lost or doubled anywhere) and ``_reference`` (the
    reference's signals and the sink's alert rows, of the sampled keys).
    With ``control`` (a row function such as ``reference.control_row``) the
    committed rows' statistics are replaced by the control's at the same
    key and tick, each rounded as the wire rounds it: the control in the
    program's place. With ``history`` (``harness.history.History``) every
    key of it is registered from the start and the reference's z-score
    lists start from its series."""
    registered = () if history is None else history.keys
    start = None if history is None else {lag: history.lists(keys, lag) for lag, _t, _i in lags}
    want = expected.multiset()
    sunk, pending, alerts = read_sunk(db_path, pending_lines)
    got = sunk + pending
    lost = sum((want - got).values()) + int(dropped)
    extra = sum((got - want).values())
    rows = read_stat_rows(db_path, keys)
    counts = {(k, ts): int(round(tpm * spec.tpm_div)) for k, ts, _lag, tpm, _st in rows
              if tpm is not None}
    ref, edges, cut_gap, unseen = reference_rows(expected, keys, spec, counts, fed_times,
                                                 registered=registered)
    if control is not None:
        cref, _e, _g, _u = reference_rows(expected, keys, spec, counts, fed_times, row_fn=control,
                                          registered=registered)
        def wire(v, digits):
            return None if v is None or math.isnan(v) else round(v, digits)

        rows = [(k, ts, lag, wire(cref[(k, ts)]["tpm"], 2),
                 dict(st, **{n: wire(cref[(k, ts)][n], 1) for n in ("average", "per75", "per95")}))
                for k, ts, lag, _tpm, st in rows if (k, ts) in cref]
    res = compare_rows(rows, ref, edges, keys, lags, spec.tpm_div, start=start)
    res["count_gap"] = max(res["count_gap"], cut_gap)
    res["missing_rows"] += unseen
    sampled = set(keys)
    unexpected = sum(n for key, n in alerts.items() if key in sampled and not res["signals"][key])
    readings = {
        "lost_records": lost, "extra_records": extra,
        "missing_rows": res["missing_rows"], "count_gap": res["count_gap"],
        "null_mismatch": res["null_mismatch"], "stat_gap": res["stat_gap"],
        "zscore_mismatch": res["zscore_mismatch"], "alerts_unexpected": unexpected,
        "rows_compared": res["rows_compared"], "bands_compared": res["bands_compared"],
    }
    out = {}
    for name, value in readings.items():
        limit = limits[name]
        ok = value >= limit if name in AT_LEAST else value <= limit
        out[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    out["_failed"] = lost + extra
    out["_reference"] = {
        "signals": sum(res["signals"].values()),
        "signal_keys": len([k for k, n in res["signals"].items() if n]),
        "alert_rows": sum(n for key, n in alerts.items() if key in sampled),
        "alert_keys": len([k for k in alerts if k in sampled]),
    }
    return out
