"""Fleet query plane: hash-routed scatter-gather serving with a durable
degraded read path (ISSUE 20).

The fleet is sharded (parallel.fleet), self-rebalancing (manager
rebalancer), and durably telemetered (obs.recorder -> obs.store), but
every read surface is per-process. This module is the single front door:
a :class:`QueryPlane` hosted on the manager (or standalone via
``python -m apmbackend_tpu.obs.queryplane``) serving ``GET /query``,
``/trace``, ``/decisions``, and ``/attrib`` fleet-wide.

Routing
-------
A single-service query (``?service=NAME`` or a ``service="NAME"``
selector label) routes via the pinned ``service_partition`` FNV-1a hash
and the live owner map to exactly the owning shard — the same placement
the write path uses, so the answer comes from the one shard that holds
the service. Everything else scatters to all shards under bounded
fan-out concurrency and merges with correct semantics:

- counters / rates / instants: colliding labelsets SUM per step,
  disjoint labelsets union (prometheus ``sum by`` over shards);
- ``histogram_quantile``: per-shard BUCKET INCREASES are fetched
  (``increase(name_bucket[..])``), summed per labelset per step, and the
  quantile is computed over the merged buckets — never by averaging
  per-shard quantiles, which is wrong for any skewed placement;
- spans and decisions dedup by identity (the recorder's keys), so a row
  that reached both a live ring and the durable store appears once.

Rebalance consistency: the owner feed is read *with a seq* before and
after every fan-out. If ownership changed underneath the query, the
query retries (bounded by ``move_retries``) so a read racing a partition
handoff neither double-counts nor drops the moving partition.

Degraded reads
--------------
A dead shard does not 404 the fleet: its slice is served from the
recorder's durable TimeSeriesStore (filtered by the shard's ``module``
label, which is then stripped so merged output is shape-identical to the
live path) and the response carries ``partial: true``, ``stale: true``,
and per-shard ``{status, freshness_s}`` so the dashboard shows *how old*
the degraded slice is instead of silently mixing epochs.

Serving
-------
A TTL read-through cache with in-flight coalescing absorbs
dashboard-repeated queries (``&cache=0`` bypasses); serving stats are
exported through the registry (``apm_queryplane_*``) and persisted
through the recorder like every other manager metric.

Import-time stdlib-only (the obs-package rule): ``service_partition``
is imported lazily from ``parallel.fleet`` on the routing path.
"""

from __future__ import annotations

import json
import math
import threading
import time
import urllib.parse
import urllib.request
from typing import Callable, Dict, List, Optional, Tuple

from .registry import MetricsRegistry, histogram_quantile
from .store import TimeSeriesStore, _EXPR_RE, eval_range, matrix_doc

# targets feed: () -> [(name, base_url)] — the FleetRecorder contract
Targets = Callable[[], List[Tuple[str, str]]]
# owner feed: () -> (seq, {partition: target name}); seq bumps only on change
Owners = Callable[[], Tuple[int, Dict[int, str]]]

_SPAN_KEY = ("trace_id", "name", "start")
_DECISION_KEY = ("trace_id", "ts", "service", "channel")


class _BadRequest(ValueError):
    """Client error: rendered as 400, never counted as a serving error."""


class _TTLCache:
    """TTL read-through cache with in-flight coalescing.

    One leader computes per key; concurrent followers wait on the
    leader's event and re-read (counted as hits — they were absorbed).
    A leader that raises releases its followers to elect a new leader,
    so one failed compute cannot wedge the key. ``ttl_s <= 0`` disables.
    """

    _MAX_ENTRIES = 512  # dashboards repeat a handful of queries; bound it

    def __init__(self, ttl_s: float):
        self.ttl_s = float(ttl_s)
        self._lock = threading.Lock()
        self._entries: Dict[tuple, Tuple[float, object]] = {}  # guarded-by: _lock
        self._inflight: Dict[tuple, threading.Event] = {}  # guarded-by: _lock

    def get_or_compute(self, key, fn):
        """-> (value, hit)."""
        if self.ttl_s <= 0:
            return fn(), False
        while True:
            with self._lock:
                now = time.monotonic()
                ent = self._entries.get(key)
                if ent is not None and ent[0] > now:
                    return ent[1], True
                ev = self._inflight.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._inflight[key] = ev
                    leader = True
                else:
                    leader = False
            if leader:
                try:
                    value = fn()
                    with self._lock:
                        if len(self._entries) >= self._MAX_ENTRIES:
                            self._entries = {
                                k: v for k, v in self._entries.items()
                                if v[0] > now
                            }
                        self._entries[key] = (time.monotonic() + self.ttl_s,
                                              value)
                    return value, False
                finally:
                    with self._lock:
                        self._inflight.pop(key, None)
                    ev.set()
            else:
                # bounded: a stuck leader must not hang followers forever
                ev.wait(timeout=30.0)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


def _pmap(fn, items: list, limit: int) -> List[Tuple[str, object]]:
    """Bounded thread fan-out; ordered ``("ok", result) | ("err", exc)``."""
    items = list(items)
    if not items:
        return []
    results: List[Tuple[str, object]] = [("err", None)] * len(items)
    sem = threading.Semaphore(max(1, int(limit)))

    def run(i, item):
        with sem:
            try:
                results[i] = ("ok", fn(item))
            except Exception as e:  # per-shard failure -> degraded, not 500
                results[i] = ("err", e)

    threads = [threading.Thread(target=run, args=(i, it), daemon=True)
               for i, it in enumerate(items)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results


def _expr_str(fn: Optional[str], q: Optional[float], name: str,
              sel: Dict[str, str], window: Optional[float]) -> str:
    """Rebuild a canonical expression string for per-shard dispatch."""
    s = name
    if sel:
        s += "{" + ",".join(f'{k}="{v}"' for k, v in sorted(sel.items())) + "}"
    if window is not None:
        s += f"[{window:g}s]"
    if fn == "histogram_quantile":
        return f"histogram_quantile({q:g}, {s})"
    if fn in ("rate", "increase"):
        return f"{fn}({s})"
    return s


def _merge_series(docs: List[dict]) -> List[dict]:
    """Sum colliding labelsets per step across shard results; union the
    disjoint ones. None means absent (identity), not zero — a step where
    every shard is None stays None."""
    merged: Dict[tuple, List[list]] = {}
    for doc in docs:
        for s in doc.get("series", []):
            key = tuple(sorted(s.get("labels", {}).items()))
            pts = s.get("points", [])
            cur = merged.get(key)
            if cur is None:
                merged[key] = [list(p) for p in pts]
                continue
            for i, p in enumerate(pts):
                if i >= len(cur):
                    cur.append(list(p))
                    continue
                v = p[1]
                if v is None:
                    continue
                cur[i][1] = v if cur[i][1] is None else cur[i][1] + v
    return [{"labels": dict(k), "points": pts}
            for k, pts in sorted(merged.items())]


def _merge_histogram(docs: List[dict], q: float) -> List[dict]:
    """Bucket-merge-then-quantile: ``docs`` are per-shard
    ``increase(name_bucket[..])`` results. Bucket increases sum per full
    labelset per step (summable; per-shard quantiles are not), then the
    quantile is computed over the merged buckets per labels-minus-le
    group — identical math to the single-store eval_range path, which is
    what makes the golden bit-equality check possible."""
    summed = _merge_series(docs)
    groups: Dict[tuple, Dict[float, List[list]]] = {}
    for s in summed:
        labels = dict(s["labels"])
        le_s = labels.pop("le", None)
        if le_s is None:
            continue
        le = math.inf if le_s in ("+Inf", "inf") else float(le_s)
        groups.setdefault(tuple(sorted(labels.items())), {})[le] = s["points"]
    series_out = []
    for key, by_le in sorted(groups.items()):
        n = max((len(p) for p in by_le.values()), default=0)
        pts_out = []
        for i in range(n):
            t = None
            buckets = []
            for le, pts in by_le.items():
                if i < len(pts):
                    t = pts[i][0]
                    if pts[i][1] is not None:
                        buckets.append((le, pts[i][1]))
            val = histogram_quantile(buckets, q) if buckets else None
            if val is not None and not math.isfinite(val):
                val = None
            pts_out.append([t, val])
        series_out.append({"labels": dict(key), "points": pts_out})
    return series_out


def _dedup_rows(rows: List[dict], key_fields: Tuple[str, ...]) -> List[dict]:
    seen = set()
    out = []
    for row in rows:
        if not isinstance(row, dict):
            continue
        k = tuple(row.get(f) for f in key_fields)
        if k in seen:
            continue
        seen.add(k)
        out.append(row)
    return out


class QueryPlane:
    """The fleet read front door; see the module docstring for semantics.

    ``targets``/``owners`` follow the recorder / OwnerMap contracts;
    ``store`` is the durable fallback (None -> dead shards stay dead);
    ``freshness`` optionally maps target name -> unixtime of its last
    successful recorder scrape (staleness honesty for degraded serves).
    ``local_attrib()`` (an /attrib snapshot) and ``local_spans(trace_id,
    n)`` (span dicts) fold the hosting process's own plane and span ring
    into /attrib and /trace, beside the shards (the manager's colocated
    producers).
    """

    def __init__(
        self,
        targets: Targets,
        *,
        owners: Optional[Owners] = None,
        store: Optional[TimeSeriesStore] = None,
        partitions: int = 0,
        partition_key: str = "service",
        registry: Optional[MetricsRegistry] = None,
        cache_ttl_s: float = 2.0,
        fanout: int = 8,
        timeout_s: float = 2.0,
        move_retries: int = 2,
        freshness: Optional[Callable[[], Dict[str, float]]] = None,
        local_attrib: Optional[Callable[[], dict]] = None,
        local_spans: Optional[Callable[[Optional[str], int], List[dict]]] = None,
        logger=None,
    ):
        self.targets = targets
        self.local_attrib = local_attrib
        self.local_spans = local_spans
        self.owners = owners
        self.store = store
        self.partitions = int(partitions)
        self.partition_key = partition_key
        self.timeout_s = float(timeout_s)
        self.fanout = max(1, int(fanout))
        self.move_retries = max(0, int(move_retries))
        self.freshness = freshness
        self._logger = logger
        self._cache = _TTLCache(cache_ttl_s)
        self._lock = threading.Lock()
        self._last_shards: Dict[str, dict] = {}  # guarded-by: _lock
        # guarded-by: _lock
        self._counts = {"requests": 0, "errors": 0, "cache_hits": 0}
        reg = registry
        self._m_requests = {
            r: reg.counter("apm_queryplane_requests_total",
                           "Fleet query plane requests served",
                           {"route": r}) if reg else None
            for r in ("query", "trace", "decisions", "attrib")
        }
        if reg is not None:
            self._m_errors = reg.counter(
                "apm_queryplane_errors_total",
                "Fleet query plane requests that failed (5xx)")
            self._m_cache_hits = reg.counter(
                "apm_queryplane_cache_hits_total",
                "Queries absorbed by the TTL cache (incl. coalesced waits)")
            self._m_fanout = reg.counter(
                "apm_queryplane_fanout_shards_total",
                "Shard sub-requests issued by the query plane")
            self._m_stale = reg.counter(
                "apm_queryplane_stale_serves_total",
                "Shard slices served from the durable store fallback")
            self._m_moves = reg.counter(
                "apm_queryplane_move_retries_total",
                "Query retries forced by an owner-map change mid-fanout")
            self._m_latency = reg.histogram(
                "apm_queryplane_latency_seconds",
                "Fleet query plane request latency")
        else:
            self._m_errors = self._m_cache_hits = self._m_fanout = None
            self._m_stale = self._m_moves = self._m_latency = None

    # -- owner feed -----------------------------------------------------------
    def _read_owners(self) -> Tuple[int, Dict[int, str]]:
        if self.owners is None:
            return 0, {}
        try:
            seq, owners = self.owners()
            return int(seq), dict(owners)
        except Exception:
            return 0, {}

    def _route_single(self, service: Optional[str], partition,
                      owners: Dict[int, str],
                      known: set) -> Tuple[Optional[str], Optional[int]]:
        """-> (owner name or None for scatter, partition or None)."""
        if partition is None and service is None:
            return None, None
        if self.partitions <= 0:
            return None, None
        if partition is not None:
            try:
                p = int(partition)
            except (TypeError, ValueError):
                raise _BadRequest("bad partition parameter")
        else:
            from ..parallel.fleet import service_partition

            p = service_partition(str(service), self.partitions)
        owner = owners.get(p)
        if owner in known:
            return owner, p
        return None, p  # owner unknown/dead-named: scatter rather than guess

    # -- shard I/O ------------------------------------------------------------
    def _fetch_json(self, url: str) -> dict:
        with urllib.request.urlopen(url, timeout=self.timeout_s) as resp:
            return json.loads(resp.read().decode("utf-8", "replace"))

    def _note_shard(self, name: str, status: str,
                    freshness_s: Optional[float]) -> None:
        with self._lock:
            self._last_shards[name] = {"status": status,
                                       "freshness_s": freshness_s}

    def _staleness(self, name: str, now: float) -> Optional[float]:
        if self.freshness is not None:
            try:
                last = self.freshness().get(name)
            except Exception:
                last = None
            if last:
                return round(max(0.0, now - float(last)), 3)
        return None

    def _fan(self, targets: List[Tuple[str, str]], live_fn,
             store_fn) -> Tuple[List[Tuple[str, object]], Dict[str, dict]]:
        """Fan ``live_fn(name, url)`` over targets under bounded
        concurrency; a failed shard degrades to ``store_fn(name)`` (the
        durable slice) instead of failing the query. Returns the ordered
        per-shard docs (None for dead) and the shard status map."""
        now = time.time()
        shard_status: Dict[str, dict] = {}
        results = _pmap(lambda t: live_fn(t[0], t[1]), targets, self.fanout)
        if self._m_fanout is not None:
            self._m_fanout.inc(len(targets))
        docs: List[Tuple[str, object]] = []
        for (name, _url), (status, res) in zip(targets, results):
            if status == "ok":
                shard_status[name] = {"status": "live", "freshness_s": 0.0}
                self._note_shard(name, "live", 0.0)
                docs.append((name, res))
                continue
            if self.store is not None:
                try:
                    doc = store_fn(name)
                except Exception:
                    doc = None
            else:
                doc = None
            if doc is not None:
                fresh = self._staleness(name, now)
                shard_status[name] = {"status": "stale", "freshness_s": fresh}
                self._note_shard(name, "stale", fresh)
                if self._m_stale is not None:
                    self._m_stale.inc()
                docs.append((name, doc))
            else:
                shard_status[name] = {"status": "dead", "freshness_s": None}
                self._note_shard(name, "dead", None)
                docs.append((name, None))
        return docs, shard_status

    @staticmethod
    def _strip_module(doc: dict) -> dict:
        """Drop the recorder's ``module`` label from a store-fallback
        eval so the degraded slice merges shape-identically with live
        shard output (bit-equality with the healthy-path answer)."""
        for s in doc.get("series", []):
            s.get("labels", {}).pop("module", None)
        return doc

    # -- /query ---------------------------------------------------------------
    def _serve_series(self, q: dict, now: float) -> dict:
        expr = q.get("series", "")
        m = _EXPR_RE.match(expr or "")
        if not m:
            raise _BadRequest(f"unsupported query expression: {expr!r}")
        try:
            start = float(q["start"]) if "start" in q else now - 300.0
            end = float(q["end"]) if "end" in q else now
            step = max(0.001, float(q.get("step", 10.0)))
        except ValueError:
            raise _BadRequest("bad start/end/step")
        fn = m.group("fn")
        qv = float(m.group("q")) if m.group("q") is not None else None
        if fn == "histogram_quantile" and qv is None:
            raise _BadRequest("histogram_quantile needs a quantile argument")
        name = m.group("name")
        from .store import parse_selector

        sel = parse_selector(m.group("sel"))
        window = float(m.group("win")) if m.group("win") else 4.0 * step
        service = q.get("service") or sel.get(self.partition_key)
        partition = q.get("partition")

        if fn == "histogram_quantile":
            base = name[:-len("_bucket")] if name.endswith("_bucket") else name
            shard_expr = _expr_str("increase", None, base + "_bucket",
                                   sel, window)
        else:
            shard_expr = _expr_str(fn, None, name, sel, window)

        def live(shard_name, url):
            qs = urllib.parse.urlencode({
                "series": shard_expr, "start": f"{start:.6f}",
                "end": f"{end:.6f}", "step": f"{step:g}"})
            return self._fetch_json(f"{url}/query?{qs}")

        def fallback(shard_name):
            sel2 = dict(sel, module=shard_name)
            return self._strip_module(eval_range(
                self.store, _expr_str("increase" if fn == "histogram_quantile"
                                      else fn, None,
                                      base + "_bucket"
                                      if fn == "histogram_quantile" else name,
                                      sel2, window),
                start, end, step))

        retries = 0
        while True:
            seq0, owners = self._read_owners()
            targets = list(self.targets() or [])
            known = {n for n, _ in targets}
            owner, _p = self._route_single(service, partition, owners, known)
            fan_targets = ([(n, u) for n, u in targets if n == owner]
                           if owner is not None else targets)
            docs, shard_status = self._fan(fan_targets, live, fallback)
            seq1, owners2 = self._read_owners()
            if seq1 == seq0 or retries >= self.move_retries:
                break
            # ownership moved mid-fanout: the slice we just merged may
            # double-count or miss the moving partition — requery against
            # the settled map (bounded; seq stability is the exit)
            retries += 1
            if self._m_moves is not None:
                self._m_moves.inc()

        useful = [d for _n, d in docs if d is not None]
        if fn == "histogram_quantile":
            series = _merge_histogram(useful, qv)
        else:
            series = _merge_series(useful)
        doc = {
            "expr": expr, "start": start, "end": end, "step": step,
            "series": series,
            "shards": shard_status,
            "shards_queried": [n for n, _ in fan_targets],
            "partial": any(v["status"] != "live"
                           for v in shard_status.values()),
            "stale": any(v["status"] == "stale"
                         for v in shard_status.values()),
            "owner_seq": seq1,
            "move_retries": retries,
        }
        return doc

    def _serve_kind(self, q: dict, now: float) -> dict:
        kind = q.get("kind")
        try:
            start = float(q["start"]) if "start" in q else now - 300.0
            end = float(q["end"]) if "end" in q else now
            limit = int(q.get("limit", 256))
            n = max(1, min(int(q.get("n", 256)), 4096))
        except ValueError:
            raise _BadRequest("bad start/end/limit/n")
        if kind in ("spans", "decisions"):
            path, field, keys = (
                ("/trace", "spans", _SPAN_KEY) if kind == "spans"
                else ("/decisions", "decisions", _DECISION_KEY))
            trace_id = q.get("trace_id")

            def live(shard_name, url):
                qs = urllib.parse.urlencode(
                    {"n": n, **({"trace_id": trace_id} if trace_id else {})})
                doc = self._fetch_json(f"{url}{path}?{qs}")
                return [r for r in doc.get(field, []) if isinstance(r, dict)]

            def fallback(shard_name):
                match = {"module": shard_name}
                if trace_id:
                    match["trace_id"] = trace_id
                rows = (self.store.spans if kind == "spans"
                        else self.store.decisions)(start, end, match, limit)
                return rows

            docs, shard_status = self._fan(list(self.targets() or []),
                                           live, fallback)
            rows = _dedup_rows(
                [r for _n, doc in docs if doc for r in doc], keys)
            if limit and len(rows) > limit:
                rows = rows[-limit:]
            return {
                "kind": kind, "start": start, "end": end, "rows": rows,
                "shards": shard_status,
                "partial": any(v["status"] != "live"
                               for v in shard_status.values()),
                "stale": any(v["status"] == "stale"
                             for v in shard_status.values()),
            }
        if kind == "names":
            def live(shard_name, url):
                doc = self._fetch_json(f"{url}/query?kind=names")
                return doc.get("names", [])

            docs, shard_status = self._fan(list(self.targets() or []),
                                           live, lambda _n: None)
            names = set()
            for _n, doc in docs:
                names.update(doc or [])
            if self.store is not None:
                names.update(self.store.series_names())
            return {"kind": "names", "names": sorted(names),
                    "shards": shard_status}
        if kind == "stats":
            body = {"kind": "stats", "plane": self.stats()}
            if self.store is not None:
                body["store"] = self.store.stats()
            return body
        raise _BadRequest(
            "need ?series=<expr> or ?kind=spans|decisions|names|stats")

    # -- /trace /decisions ----------------------------------------------------
    def _serve_ring(self, q: dict, kind: str, now: float) -> dict:
        path, field, keys = (
            ("/trace", "spans", _SPAN_KEY) if kind == "spans"
            else ("/decisions", "decisions", _DECISION_KEY))
        trace_id = q.get("trace_id")
        try:
            n = max(1, min(int(q.get("n", 256)), 4096))
        except ValueError:
            raise _BadRequest("bad n parameter")

        children: Dict[str, object] = {}  # rows per live shard, or its error

        def live(shard_name, url):
            qs = urllib.parse.urlencode(
                {"n": n, **({"trace_id": trace_id} if trace_id else {})})
            try:
                doc = self._fetch_json(f"{url}{path}?{qs}")
            except Exception as e:
                children[shard_name] = f"error: {e!r}"
                raise
            rows = [r for r in doc.get(field, []) if isinstance(r, dict)]
            if kind == "spans":
                for r in rows:
                    r.setdefault("module", shard_name)
            children[shard_name] = len(rows)
            return rows

        def fallback(shard_name):
            match = {"module": shard_name}
            if trace_id:
                match["trace_id"] = trace_id
            return (self.store.spans if kind == "spans"
                    else self.store.decisions)(0.0, now + 1.0, match, n)

        docs, shard_status = self._fan(list(self.targets() or []),
                                       live, fallback)
        rows = [r for _n, doc in docs if doc for r in doc]
        if kind == "spans" and self.local_spans is not None:
            rows.extend(self.local_spans(trace_id, n))
        rows = _dedup_rows(rows, keys)
        body = {
            "fleet": True, "count": len(rows), field: rows,
            "shards": shard_status,
            "partial": any(v["status"] != "live"
                           for v in shard_status.values()),
            "stale": any(v["status"] == "stale"
                         for v in shard_status.values()),
        }
        if kind == "spans":
            # stitched: each sampled transaction's spans across modules,
            # ordered along its journey
            body["children"] = children
            traces: Dict[object, List[dict]] = {}
            for r in rows:
                traces.setdefault(r.get("trace_id"), []).append(r)
            for spans in traces.values():
                spans.sort(key=lambda r: (r.get("start", 0.0), r.get("end", 0.0)))
            body["traces"] = traces
            body["trace_count"] = len(traces)
        return body

    # -- /attrib --------------------------------------------------------------
    def _attrib_from_store(self, shard_name: str, now: float) -> Optional[dict]:
        """Synthesize a mergeable /attrib snapshot for a dead shard from
        its last recorded ``apm_stage_*`` counters — coarse (no
        occupancy, window unknown) but it keeps the dead shard's stage
        seconds in the fleet bottleneck estimate instead of vanishing."""
        stages: Dict[str, dict] = {}
        found = False
        for metric, field in (
            ("apm_stage_busy_seconds_total", "busy_s"),
            ("apm_stage_blocked_seconds_total", "blocked_s"),
            ("apm_stage_idle_seconds_total", "idle_s"),
            ("apm_stage_events_total", "events"),
        ):
            groups = self.store.series_points(
                metric, 0.0, now + 1.0, {"module": shard_name})
            for key, pts in groups.items():
                if not pts:
                    continue
                stage = dict(key).get("stage", "?")
                st = stages.setdefault(
                    stage, {"busy_s": 0.0, "blocked_s": 0.0, "idle_s": 0.0,
                            "events": 0})
                val = pts[-1][1]
                st[field] = int(val) if field == "events" else float(val)
                found = True
        if not found:
            return None
        window = max((st["busy_s"] + st["blocked_s"] + st["idle_s"]
                      for st in stages.values()), default=0.0)
        return {"module": shard_name, "window_s": round(window, 3),
                "stages": stages, "occupancy": {}}

    def _serve_attrib(self, q: dict, now: float) -> dict:
        from .attrib import merge_snapshots

        child_status: Dict[str, str] = {}

        def live(shard_name, url):
            try:
                snap = self._fetch_json(f"{url}/attrib")
            except Exception as e:
                child_status[shard_name] = f"error: {e!r}"
                raise
            if snap.get("module") in (None, "", "apm"):
                snap["module"] = shard_name
            child_status[shard_name] = "ok"
            return snap

        def fallback(shard_name):
            return self._attrib_from_store(shard_name, now)

        docs, shard_status = self._fan(list(self.targets() or []),
                                       live, fallback)
        snaps = [d for _n, d in docs if d]
        if self.local_attrib is not None:
            snaps.insert(0, self.local_attrib())
        body = merge_snapshots(snaps)
        body["child_status"] = child_status
        body["shards"] = shard_status
        body["partial"] = any(v["status"] != "live"
                              for v in shard_status.values())
        body["stale"] = any(v["status"] == "stale"
                            for v in shard_status.values())
        return body

    # -- route plumbing -------------------------------------------------------
    def _cache_key(self, route: str, q: dict, now: float) -> tuple:
        ttl = self._cache.ttl_s
        items = {k: v for k, v in q.items() if k != "cache"}
        if route == "query" and q.get("series") and ttl > 0:
            # default now-anchored ranges quantize to the TTL grid so the
            # dashboard's repeated "last 5 minutes" shares one entry —
            # exactly the staleness a TTL cache already promises
            if "end" not in items:
                items["end"] = f"{math.floor(now / ttl) * ttl:.3f}"
            if "start" not in items:
                items["start"] = f"{float(items['end']) - 300.0:.3f}"
        return (route,) + tuple(sorted(items.items()))

    def _wrap(self, route: str, serve, cache: bool = True):
        def handler(query):
            q = {k: (v[0] if isinstance(v, list) else v)
                 for k, v in query.items()}
            t0 = time.monotonic()
            with self._lock:
                self._counts["requests"] += 1
            if self._m_requests.get(route) is not None:
                self._m_requests[route].inc()
            now = time.time()
            try:
                if not cache or q.get("cache") == "0" or self._cache.ttl_s <= 0:
                    body, hit = serve(q, now), False
                else:
                    body, hit = self._cache.get_or_compute(
                        self._cache_key(route, q, now), lambda: serve(q, now))
                if hit:
                    with self._lock:
                        self._counts["cache_hits"] += 1
                    if self._m_cache_hits is not None:
                        self._m_cache_hits.inc()
                    body = dict(body)
                body["cached"] = hit
                if route == "query" and q.get("format") == "matrix" \
                        and "series" in body:
                    body = matrix_doc(body)
                return 200, "application/json", json.dumps(body, default=repr)
            except _BadRequest as e:
                return 400, "text/plain; charset=utf-8", f"{e}\n"
            except Exception as e:
                with self._lock:
                    self._counts["errors"] += 1
                if self._m_errors is not None:
                    self._m_errors.inc()
                if self._logger:
                    self._logger.warning("queryplane: /%s failed: %s",
                                         route, e)
                return 500, "text/plain; charset=utf-8", \
                    f"query plane error: {type(e).__name__}\n"
            finally:
                if self._m_latency is not None:
                    self._m_latency.observe(time.monotonic() - t0)

        return handler

    def make_routes(self) -> Dict[str, Callable]:
        """Route table for :meth:`TelemetryServer.add_route` — mounting
        these on the manager OVERRIDES its per-process /query /trace
        /decisions /attrib with the fleet-wide versions."""
        def serve_query(q, now):
            if q.get("kind"):
                return self._serve_kind(q, now)
            if q.get("series"):
                return self._serve_series(q, now)
            raise _BadRequest(
                "need ?series=<expr> or ?kind=spans|decisions|names|stats")

        return {
            "/query": self._wrap("query", serve_query),
            "/trace": self._wrap(
                "trace", lambda q, now: self._serve_ring(q, "spans", now)),
            "/decisions": self._wrap(
                "decisions",
                lambda q, now: self._serve_ring(q, "decisions", now)),
            # uncached: the body is the window's live stage table and
            # each child's liveness at this moment
            "/attrib": self._wrap("attrib", self._serve_attrib, cache=False),
        }

    # -- introspection --------------------------------------------------------
    def stats(self) -> dict:
        seq, owners = self._read_owners()
        with self._lock:
            return {
                "requests": self._counts["requests"],
                "errors": self._counts["errors"],
                "cache_hits": self._counts["cache_hits"],
                "cache_entries": len(self._cache),
                "cache_ttl_s": self._cache.ttl_s,
                "owner_seq": seq,
                "owned_partitions": len(owners),
                "partitions": self.partitions,
                "shards": dict(self._last_shards),
            }

    def health(self) -> dict:
        """Healthz section: degraded shards flag the plane as degraded
        (still ``ok`` — partial serving is the design, not a failure)."""
        st = self.stats()
        st["ok"] = True
        st["degraded"] = any(v.get("status") != "live"
                             for v in st["shards"].values())
        return st


# ---------------------------------------------------------------------------
# Standalone entry point: python -m apmbackend_tpu.obs.queryplane
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    """Run the plane standalone against explicit targets — the
    off-manager deployment (a dashboard tier that must survive manager
    restarts). Owner feed: poll the manager's ``/fleet`` text for
    ``apm_fleet_partition_owner`` rows; shard ids map to targets by the
    ``shard<k>`` naming convention (unknown names just scatter)."""
    import argparse

    from .exporter import TelemetryServer

    ap = argparse.ArgumentParser(prog="apmbackend_tpu.obs.queryplane")
    ap.add_argument("--target", action="append", default=[],
                    metavar="NAME=URL", help="shard endpoint (repeatable)")
    ap.add_argument("--store", default=None,
                    help="recorder store directory (durable fallback)")
    ap.add_argument("--port", type=int, default=9464)
    ap.add_argument("--partitions", type=int, default=0)
    ap.add_argument("--partition-key", default="service")
    ap.add_argument("--fleet-url", default=None,
                    help="manager /fleet URL for the live owner feed")
    ap.add_argument("--config", default=None,
                    help="config JSON; reads its queryPlane section")
    args = ap.parse_args(argv)

    qp_cfg = {}
    if args.config:
        with open(args.config, "r", encoding="utf-8") as fh:
            qp_cfg = (json.load(fh) or {}).get("queryPlane", {}) or {}

    static = []
    for spec in args.target:
        name, _, url = spec.partition("=")
        if not url:
            ap.error(f"--target needs NAME=URL, got {spec!r}")
        static.append((name, url.rstrip("/")))

    owners_fn = None
    if args.fleet_url:
        from ..parallel.fleet import OwnerMap, owner_map_from_fleet_text

        omap = OwnerMap()
        refresh_s = float(qp_cfg.get("ownerRefreshSeconds", 5.0))
        state = {"ts": 0.0}

        def owners_fn():
            now = time.monotonic()
            if now - state["ts"] >= refresh_s:
                state["ts"] = now
                try:
                    with urllib.request.urlopen(args.fleet_url,
                                                timeout=2.0) as resp:
                        text = resp.read().decode("utf-8", "replace")
                    omap.update({p: f"shard{s}" for p, s in
                                 owner_map_from_fleet_text(text).items()})
                except Exception:
                    pass  # keep serving on the last good map
            return omap.read()

    store = None
    if args.store:
        store = TimeSeriesStore(args.store)

    reg = MetricsRegistry()
    plane = QueryPlane(
        lambda: static,
        owners=owners_fn,
        store=store,
        partitions=args.partitions,
        partition_key=args.partition_key,
        registry=reg,
        cache_ttl_s=float(qp_cfg.get("cacheTtlSeconds", 2.0)),
        fanout=int(qp_cfg.get("fanoutConcurrency", 8)),
        timeout_s=float(qp_cfg.get("timeoutSeconds", 2.0)),
        move_retries=int(qp_cfg.get("moveRetries", 2)),
    )
    server = TelemetryServer(registry=reg, module="queryplane",
                             port=args.port)
    for path, fn in plane.make_routes().items():
        server.add_route(path, fn)
    server.add_health("queryplane", plane.health)
    port = server.start()
    print(f"query plane serving on http://127.0.0.1:{port} "
          f"(/query /trace /decisions /attrib) over {len(static)} targets",
          flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
