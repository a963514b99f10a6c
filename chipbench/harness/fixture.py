"""Seeded WildFly fleet logs: the benchmark's own copy of the fixture generator.

Copied from ``apmbackend_tpu/ingest/replay.py`` (``FixtureGenerator`` and
``write_fixture_logs``) so that a change to the program cannot move the
yardstick. The line formats are the original's. How transactions are
drawn is data: a traffic file's ``records`` section (``RECORDS`` below),
whose defaults are the original's: per transaction a uniform service,
elapsed uniform in 50-1200 ms, 50% SOAP/EJB pairs (soap_io header +
server.log CommonTiming), 30% standard CommonTiming pairs on the app log,
20% audit-trail blocks whose non-Provider records go to the DB only; log
time advances ``10 / tx_per_bucket`` s per transaction with +-50% jitter.
With those defaults the draws, and so the logs, are the original's.

One departure: log ids (``jb...``, ``AUTR...``) carry the server's name.
The original numbers them from 1 on every server, so a SOAP header of one
JVM could hand its account to a record of another with the same id; a
fleet's JVMs do not share ids.

What the copy adds, so that the harness can feed a prefix and know what it
fed: per file the byte offset at the end of every line and the log time at
which the line is released (the running maximum of the line's own stamps,
so a file is appended in its generation order), and per record the file
and index of the line that completes it. Nothing here imports JAX or the
program: the generating worker processes use it as is.
"""

from __future__ import annotations

import bisect
import calendar
import heapq
import itertools
import os
import random
from datetime import datetime, timedelta
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

START = datetime(2024, 1, 10, 9, 0, 0)  # the original's fixed log epoch

ROUTE_STATS = 0  # bound for the engine
ROUTE_DB = 1     # audit-trail record of a non-Provider service: DB only


def _log_ts(dt: datetime) -> str:
    return dt.strftime("%Y-%m-%d %H:%M:%S,") + f"{dt.microsecond // 1000:03d}"


def _local_ms(dt: datetime) -> int:
    """Epoch ms of a log timestamp, which the parser reads as local time."""
    return int(dt.replace(microsecond=0).timestamp()) * 1000 + dt.microsecond // 1000


def _utc_ms(dt: datetime) -> int:
    """Epoch ms of an audit-trail stamp (written with a -00:00 offset)."""
    return calendar.timegm(dt.timetuple()) * 1000 + dt.microsecond // 1000


def _iso_ts(dt: datetime) -> str:
    return dt.strftime("%Y-%m-%dT%H:%M:%S.") + f"{dt.microsecond // 1000:03d}" + "-00:00"


class _File:
    """One log file being generated: its lines, their end offsets and their
    own log-time stamps (ms)."""

    __slots__ = ("lines", "ends", "stamps", "size")

    def __init__(self):
        self.lines: List[str] = []
        self.ends: List[int] = []
        self.stamps: List[int] = []
        self.size = 0

    def put(self, line: str, stamp_ms: int) -> int:
        self.lines.append(line)
        self.size += len(line.encode("utf-8")) + 1
        self.ends.append(self.size)
        self.stamps.append(stamp_ms)
        return len(self.lines) - 1


class ServerLogs:
    """One server's generated logs and the records the parser makes of them.

    ``files[name]`` = (blob, line end offsets, release ms); ``records`` are
    parallel arrays: service key, route, end epoch ms, elapsed ms, file name
    and index of the completing line, and for a SOAP/EJB record the soap_io
    line that carries its account number (the parser holds the record until
    it has read both)."""

    def __init__(self, server: str):
        self.server = server
        self.files: Dict[str, Tuple[bytes, np.ndarray, np.ndarray]] = {}
        self.rec_service: List[str] = []
        self.rec_route: List[int] = []
        self.rec_end_ms: List[int] = []
        self.rec_elapsed: List[int] = []
        self.rec_file: List[str] = []
        self.rec_line: List[int] = []
        self.rec_acct_file: List[str] = []  # SOAP/EJB: the soap_io line carrying the account
        self.rec_acct_line: List[int] = []

    def write(self, out_dir: str) -> Dict[str, str]:
        os.makedirs(out_dir, exist_ok=True)
        paths = {}
        for name, (blob, _ends, _rel) in self.files.items():
            p = os.path.join(out_dir, name)
            with open(p, "wb") as fh:
                fh.write(blob)
            paths[name] = p
        return paths


# The traffic file's ``records`` section: how a server's transactions are
# drawn. Each key left out takes the original fixture's value, given here.
RECORDS = {
    # shares of the three record kinds (normalised to sum to 1)
    "kinds": {"soap_ejb": 0.5, "common_timing": 0.3, "audit_trail": 0.2},
    "elapsed_ms": [50, 1200],      # uniform, inclusive
    "riskid_share": 0.2,           # SOAP headers with the account as <key>/<value>
    "gap_jitter": 0.5,             # gap between transactions: mean x U(1 - j, 1 + j)
    # service weights: Zipf exponent over the ``active`` names (all of them
    # when null); every ``shift_every_s`` of log time the active window moves
    # ``shift_by`` names on, so a hot set moves and, with fewer names active
    # than the server has, keys leave and come back
    "services": {"zipf_s": 0.0, "active": None, "shift_every_s": 0, "shift_by": 1},
    # arrival-rate multiplier over log time: [start_s, multiplier] steps,
    # repeating every ``period_s`` (0: the last step holds for good)
    "rate": {"period_s": 0, "steps": [[0, 1.0]]},
    # a share of transactions whose lines reach their logs ``delay_ms`` late
    "late": {"share": 0.0, "delay_ms": [0, 0]},
    # elapsed times scaled by ``elapsed_factor`` for the first ``services``
    # names of the first ``servers`` servers (all when null), in [from_s, to_s)
    # of log time
    "anomalies": [],
}


def records_params(records: Optional[dict]) -> dict:
    """``RECORDS`` overlaid with a traffic file's ``records`` section."""
    out = {k: (dict(v) if isinstance(v, dict) else v) for k, v in RECORDS.items()}
    for k, v in (records or {}).items():
        if k not in out:
            raise ValueError(f"unknown records parameter {k!r}; known: {sorted(out)}")
        if isinstance(out[k], dict) and k != "kinds":
            unknown = set(v) - set(out[k])
            if unknown:
                raise ValueError(f"unknown records.{k} parameters {sorted(unknown)}")
            out[k].update(v)
        else:
            out[k] = v
    return out


def _service_picker(n: int, sp: dict, rng: random.Random):
    """(log seconds) -> index of the service a transaction calls."""
    s = float(sp["zipf_s"])
    active = min(int(sp["active"] or n), n)
    every = float(sp["shift_every_s"])
    by = int(sp["shift_by"])
    if s == 0.0 and active == n and every <= 0:
        return lambda _log_s: rng.randrange(n)  # the original's draw
    cum = list(itertools.accumulate(1.0 / (r + 1) ** s for r in range(active)))

    def pick(log_s: float) -> int:
        shift = int(log_s // every) * by if every > 0 else 0
        r = min(bisect.bisect_right(cum, rng.random() * cum[-1]), active - 1)
        return (shift + r) % n

    return pick


def _rate(rate: dict):
    """(log seconds) -> arrival-rate multiplier."""
    steps = sorted((float(a), float(m)) for a, m in rate["steps"])
    starts = [a for a, _m in steps]
    period = float(rate["period_s"])
    if any(m <= 0 for _a, m in steps):
        raise ValueError("rate multipliers have to be positive")

    def at(log_s: float) -> float:
        x = log_s % period if period > 0 else log_s
        return steps[max(bisect.bisect_right(starts, x) - 1, 0)][1]

    return at


def generate_server(server: str, services: Sequence[str], n_transactions: int,
                    tx_per_bucket: float, seed: int, records: Optional[dict] = None,
                    server_index: int = 0, start: Optional[datetime] = None) -> ServerLogs:
    """The fixture's transaction stream for one server (write_fixture_logs
    with ``tx_per_bucket`` set and no anomaly), drawn as ``records`` says."""
    p = records_params(records)
    rng = random.Random(seed + 1)
    t = start or START
    t0 = t
    next_id = 0
    files: Dict[str, _File] = {}
    out = ServerLogs(server)

    def f(name: str) -> _File:
        if name not in files:
            files[name] = _File()
        return files[name]

    def note(service: str, route: int, end_ms: int, elapsed: int, fname: str, line: int,
             acct_file: str = "", acct_line: int = -1) -> None:
        out.rec_service.append(service)
        out.rec_route.append(route)
        out.rec_end_ms.append(end_ms)
        out.rec_elapsed.append(elapsed)
        out.rec_file.append(fname)
        out.rec_line.append(line)
        out.rec_acct_file.append(acct_file)
        out.rec_acct_line.append(acct_line)

    kinds = p["kinds"]
    total = sum(float(kinds.get(k, 0.0)) for k in ("soap_ejb", "common_timing", "audit_trail"))
    if set(kinds) - {"soap_ejb", "common_timing", "audit_trail"} or total <= 0:
        raise ValueError(f"records.kinds: {kinds}")
    c_soap = float(kinds.get("soap_ejb", 0.0)) / total
    c_ct = c_soap + float(kinds.get("common_timing", 0.0)) / total
    el_lo, el_hi = (int(x) for x in p["elapsed_ms"])
    riskid_share = float(p["riskid_share"])
    jitter = float(p["gap_jitter"])
    pick = _service_picker(len(services), p["services"], rng)
    rate = _rate(p["rate"])
    late_share = float(p["late"]["share"])
    late_lo, late_hi = (int(x) for x in p["late"]["delay_ms"])
    anomalies = [(a.get("servers"), int(a["services"]), float(a["from_s"]), float(a["to_s"]),
                  float(a["elapsed_factor"])) for a in p["anomalies"]]
    held: List[tuple] = []  # late transactions: (release ms, order, write)

    soap_name = f"soap_io_{server}.log"
    app_name = f"app_{server}.log"
    mean_s = 10.0 / float(tx_per_bucket)
    for _ in range(n_transactions):
        log_s = (t - t0).total_seconds()
        idx = pick(log_s)
        service = services[idx]
        elapsed = rng.randint(el_lo, el_hi)
        for a_servers, a_services, a_from, a_to, factor in anomalies:
            if ((a_servers is None or server_index < int(a_servers)) and idx < a_services
                    and a_from <= log_s < a_to):
                elapsed = max(1, int(round(elapsed * factor)))
        acct = rng.randint(10**8, 10**9 - 1)
        kind = rng.random()
        delay = 0
        if late_share > 0 and rng.random() < late_share:
            delay = rng.randint(late_lo, late_hi)
        end = t + timedelta(milliseconds=elapsed)
        t_ms, end_ms = _local_ms(t), _local_ms(end)
        next_id += 1
        log_id = f"jb{server}{next_id:08d}"
        if kind < c_soap:
            riskid = rng.random() < riskid_share

            def write(log_id=log_id, t=t, end=end, t_ms=t_ms, end_ms=end_ms, service=service,
                      elapsed=elapsed, acct=acct, riskid=riskid):
                soap = f(soap_name)
                soap.put(f"=== jbossId={log_id} ts={_log_ts(t)} IO=I ===", t_ms)
                if riskid:
                    soap.put("    <key>AccountNumber</key>", t_ms)
                    acct_line = soap.put(f"    <value>{acct}</value>", t_ms)
                else:
                    acct_line = soap.put(f"    <accountNumber>{acct}</accountNumber>", t_ms)
                soap.put("  <payload>...</payload>", t_ms)
                soap.put(f"=== jbossId={log_id} ts={_log_ts(end)} IO=O ===", end_ms)
                srv = f("server.log")
                srv.put(f"[{log_id}] {_log_ts(t)} INFO [CommonTiming] The EJB timing entry "
                        f"has begun for method {service}", t_ms)
                last = srv.put(f"[{log_id}] {_log_ts(end)} INFO [CommonTiming] Total time for "
                               f"EJB {service} call: {elapsed} ms", end_ms)
                note(f"S:{service}", ROUTE_STATS, end_ms, elapsed, "server.log", last,
                     soap_name, acct_line)
        elif kind < c_ct:
            def write(log_id=log_id, t=t, end=end, t_ms=t_ms, end_ms=end_ms, service=service,
                      elapsed=elapsed, acct=acct):
                app = f(app_name)
                meta = f"[ch:7:{acct}] "
                app.put(f"[{log_id}] {_log_ts(t)} {meta}INFO CommonTiming::Start {service} "
                        f"begin", t_ms)
                last = app.put(f"[{log_id}] {_log_ts(end)} {meta}INFO CommonTiming::Stop "
                               f"{service} completed in time: {elapsed} ms", end_ms)
                note(service, ROUTE_STATS, end_ms, elapsed, app_name, last)
        else:
            subs = [(service, elapsed), ("bcottag", rng.randint(5, 50))]

            def write(log_id=log_id, t=t, acct=acct, subs=subs, next_id=next_id):
                block_end = _local_ms(t + timedelta(milliseconds=sum(ms for _s, ms in subs)))
                app = f(app_name)
                autr_id = f"AUTR{server}{next_id:06d}"
                app.put(f"[{log_id}] {_log_ts(t)} [ch:9:{acct}] INFO  auditTrailId={autr_id} "
                        f"begin", block_end)
                app.put(f"Audit Trail id : {autr_id}", block_end)
                app.put("summary: RequestTrace [stopWatchList=", block_end)
                for svc, ms in subs:
                    app.put(f"{svc} :[{ms} millis] step", block_end)
                app.put("]", block_end)
                app.put("<stopWatchList>", block_end)
                ts = t
                for svc, ms in subs:
                    t_end = ts + timedelta(milliseconds=ms)
                    app.put(f"  <name>{svc}</name>", block_end)
                    app.put(f"  <startTime>{_iso_ts(ts)}</startTime>", block_end)
                    stop = app.put(f"  <stopTime>{_iso_ts(t_end)}</stopTime>", block_end)
                    ts = t_end
                    # the parser emits each sub-service's record at its stopTime line
                    if "Provider[" in svc:
                        note(svc.replace("Provider[", "Provider:").replace("]", ""),
                             ROUTE_STATS, _utc_ms(ts), ms, app_name, stop)
                    else:
                        note(svc, ROUTE_DB, _utc_ms(ts), ms, app_name, stop)
                app.put("</stopWatchList>", block_end)

        while held and held[0][0] <= t_ms:  # late lines now due
            heapq.heappop(held)[2]()
        if delay:
            heapq.heappush(held, (end_ms + delay, next_id, write))
        else:
            write()
        gap = mean_s / rate(log_s)
        t += timedelta(seconds=rng.uniform((1.0 - jitter) * gap, (1.0 + jitter) * gap))
    while held:
        heapq.heappop(held)[2]()

    for name, fl in files.items():
        blob = ("\n".join(fl.lines) + "\n").encode("utf-8")
        ends = np.asarray(fl.ends, np.int64)
        release = np.maximum.accumulate(np.asarray(fl.stamps, np.int64))
        out.files[name] = (blob, ends, release)
    return out
