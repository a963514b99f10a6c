"""Log-correlation parser: raw JVM log lines -> complete TxEntry records.

Reproduces the correlation semantics of stream_parse_transactions.js (the
reference's design notes :3-44):

- **SOAP logs** build a logId -> accountNumber map: an ``IO=I`` header opens a
  per-file context carrying the logId; a later ``<accountNumber>`` (or the
  riskid two-line ``<key>AccountNumber</key>`` / ``<value>`` form) saves the
  account number (:352-376).
- **CommonTiming entry/exit join**: entry lines park a partial record keyed
  (logId, service) in a TTL cache; the exit line joins it with the account
  cache into a full record (:378-446 EJB form, :451-565 standard form). A
  missing account number parks the joined record in a second, shorter-TTL
  cache that is flushed when the SOAP parser later finds the number
  (saveAcctNum backfill :294-327) or emitted without it on expiry (:226-239).
- **BAF salvage**: exit lines on BAF logs may carry the account number inside
  bracketed metadata before INFO; used as a last resort (:486-504).
- **Audit-trail state machine** (APP logs): a mapping line links autrId ->
  logId; the "Audit Trail id :" line activates a per-file context; the
  RequestTrace elapsed section collects per-subservice elapsed arrays (same
  subservice can repeat, consumed FIFO); the stopWatchList XML supplies
  start/stop timestamps per subservice; each completed subservice emits a
  record, with non-Provider records routed straight to the DB queue
  (insert_to_db) to skip stats processing (:578-731).
- Emitted records may lack logId/acctNum/startTs; startTs falls back to
  endTs - elapsed (:264-290). ``Provider[...]`` is normalized to
  ``Provider:...`` and ``S:`` marks top-level (:258,274,282).

Output is roughly ordered only (cache timeouts) — downstream re-orders via the
min-heap, like the reference (:7, stream_calc_stats.js:136-155).
"""

from __future__ import annotations

import os
import re
import threading
import time
from dataclasses import dataclass, field
from datetime import datetime
from typing import Callable, Dict, List, Optional, Union

from ..entries import TxEntry, format_tx_line
from .ttlcache import TTLCache

# Kill switch for the native (C++) ingest fast path — same pattern as
# APM_PCT_NO_RADIX: set APM_PARSE_NO_NATIVE=1 to force the pure-Python
# reference implementation (read_lines degrades to a per-line loop and the
# correlation record cache stays a Python TTLCache). Both paths produce
# bit-identical TxEntry streams (tests/test_parser_native_diff.py).
_NO_NATIVE_ENV = "APM_PARSE_NO_NATIVE"

_TOPLEVEL_RE = re.compile(r"^S:")
_PROVIDER_RE = re.compile(r"Provider\[", re.IGNORECASE)

_SOAP_IN_RE = re.compile(r"^=== jbossId.*IO=I")
_SOAP_OUT_RE = re.compile(r"^=== jbossId.*IO=O")
_SOAP_ACCT_RE = re.compile(r"<accountNumber>", re.IGNORECASE)
_SOAP_ALT_KEY_RE = re.compile(r"<key>AccountNumber</key>", re.IGNORECASE)
_SOAP_ALT_VALUE_RE = re.compile(r"<value>")

_EJB_ENTRY_RE = re.compile(r"INFO *\[CommonTiming] The EJB")
_EJB_EXIT_RE = re.compile(r"INFO *\[CommonTiming] Total time")
_CT_ENTRY_RE = re.compile(r"INFO *CommonTiming::Start")
_CT_EXIT_RE = re.compile(r"INFO *CommonTiming::Stop")

_BAF_META_RE = re.compile(r"\[[^ ]+] +INFO ")

_AUTR_MAP_RE = re.compile(r"INFO  auditTrailId=")
_AUTR_LINE_RE = re.compile(r"^Audit Trail id *:")
_ELAPSED_START_RE = re.compile(r": RequestTrace \[stopWatchList=")
_ELAPSED_END_RE = re.compile(r"^]")
_SW_XML_START_RE = re.compile(r"<stopWatchList>")
_SW_XML_END_RE = re.compile(r"</stopWatchList>")
_SW_NAME_RE = re.compile(r"<name>")
_SW_START_RE = re.compile(r"<startTime>")
_SW_STOP_RE = re.compile(r"<stopTime>")

_SOAP_FILE_RE = re.compile(r"soap_io")
_SERVER_FILE_RE = re.compile(r"server\.log")

# one alternation pass as PRE-FILTER *and* dispatcher: most lines carry no
# timing marker at all (payload/noise), and for them a single scan replaces
# up to four sequential searches. Each alternative is a GROUP so the match
# also identifies WHICH marker won at the leftmost position (m.lastindex,
# 1-4) — the regex engine tries alternatives left-to-right per position, so
# lastindex is the highest-priority marker AT the leftmost occurrence. The
# reference ladder (stream_parse_transactions.js:741-812) wants the
# highest-priority marker occurring ANYWHERE, which can differ only when a
# higher-priority marker occurs strictly AFTER the leftmost one — so only
# the (rare) patterns ABOVE lastindex are re-searched, from the match
# position on, instead of discarding the match and re-running the whole
# ladder (the double-regex this replaces).
_SERVER_DISPATCH_RE = re.compile(
    r"(INFO *\[CommonTiming] The EJB)"
    r"|(INFO *\[CommonTiming] Total time)"
    r"|(INFO *CommonTiming::Start)"
    r"|(INFO *CommonTiming::Stop)"
)
# ladder priorities 1..3 for the above re-search (priority 4 never needs one)
_LADDER_RES = (_EJB_ENTRY_RE, _EJB_EXIT_RE, _CT_ENTRY_RE)

_ISO_TZ_RE = re.compile(r"T.*-")
_DIGITS_RE = re.compile(r"^[0-9]+$")


class ConsumerError(Exception):
    """A downstream on_record consumer raised — NOT a malformed log line."""


_date_ms_cache: Dict[str, str] = {}
_minute_ms_cache: Dict[tuple, int] = {}


def convert_log_date_to_ms(date_str: str) -> str:
    """'' for falsy; audit ISO-with-offset or 'YYYY-MM-DD HH:MM:SS,mmm' (local
    time) -> epoch ms (stream_parse_transactions.js:242-256).

    This runs twice per emitted record — one of the two dominant
    per-emission costs — so it is memoized twice over, with NO numeric
    drift between the parser backends (both share this function):

    - a string-keyed memo (audit-trail blocks chain each stopTime into the
      next startTime; entry timestamps are re-parsed at exit join);
    - for the local-time form, a minute-keyed epoch cache: the expensive
      ``datetime(...).timestamp()`` runs once per distinct minute and the
      seconds/millis are added as exact integers. A minute-aligned
      timestamp is an integral float (no mantissa rounding) and DST
      transitions land on whole minutes, so ``minute_ms + s*1000 + mmm``
      IS the exact epoch value — strictly tighter than the previous
      per-call float path, whose *1000 product could truncate one ulp shy
      of the integer."""
    if not date_str:
        return ""
    cached = _date_ms_cache.get(date_str)
    if cached is not None:
        return cached
    if _ISO_TZ_RE.search(date_str):
        out = str(int(datetime.fromisoformat(date_str).timestamp() * 1000))
    else:
        ds = date_str.strip()
        sec = -1
        if (
            len(ds) == 23 and ds[4] == "-" and ds[7] == "-"
            and ds[10] == " " and ds[13] == ":" and ds[16] == ":"
            and ds[19] == ","
        ):
            # fixed-layout scan of the canonical WildFly form: re.split was
            # the single hottest memo-miss cost, and dense streams make
            # almost every call a miss (unique millis). The minute cache is
            # keyed by the 16-char prefix, so the hot path is one slice, one
            # dict hit and two int()s. Any non-digit slice falls through to
            # the general splitter, so junk input keeps the exact legacy
            # error behaviour (the prefix of a malformed minute can never be
            # cached — only successful parses insert).
            try:
                sec, ms = int(ds[17:19]), int(ds[20:23])
                mkey = ds[:16]
                base = _minute_ms_cache.get(mkey)
                if base is None:
                    base = int(datetime(
                        int(ds[:4]), int(ds[5:7]), int(ds[8:10]),
                        int(ds[11:13]), int(ds[14:16]),
                    ).timestamp()) * 1000
                    if len(_minute_ms_cache) >= 4096:
                        _minute_ms_cache.clear()
                    _minute_ms_cache[mkey] = base
            except ValueError:
                sec = -1
        if sec < 0:
            # general form: whitespace runs, fancy widths — the legacy path
            # (str prefix vs 5-tuple keys cannot collide in the shared cache)
            parts = re.split(r"-|\s+|:|,", ds)
            mkey = (parts[0], parts[1], parts[2], parts[3], parts[4])
            sec, ms = int(parts[5]), int(parts[6])
            base = _minute_ms_cache.get(mkey)
            if base is None:
                dt = datetime(
                    int(parts[0]), int(parts[1]), int(parts[2]),
                    int(parts[3]), int(parts[4]),
                )
                base = int(dt.timestamp()) * 1000
                if len(_minute_ms_cache) >= 4096:
                    _minute_ms_cache.clear()
                _minute_ms_cache[mkey] = base
        if not (0 <= sec <= 59 and 0 <= ms <= 999):
            # datetime() would have rejected these; keep the raise
            raise ValueError(f"second/millisecond out of range: {date_str!r}")
        out = str(base + sec * 1000 + ms)
    if len(_date_ms_cache) >= 16384:  # bounded: log time advances, keys churn
        _date_ms_cache.clear()
    _date_ms_cache[date_str] = out
    return out


def _strip_brackets(token: str) -> str:
    return token.replace("[", "").replace("]", "")


def _xml_text(line: str) -> str:
    """Text content of a single-tag XML line: strip the closing tag FIRST,

    then everything through the remaining (opening) '>' — order matters with
    greedy matching (stream_parse_transactions.js:669,677,682). Implemented
    with find/rfind, exactly equivalent to the original
    ``re.sub(r".*>", "", re.sub(r"</.*", "", line), count=1)``: the inner
    sub cuts at the FIRST "</" (the greedy tail eats the rest), the outer
    strips through the LAST '>' of the remainder."""
    cut = line.find("</")
    if cut >= 0:
        line = line[:cut]
    gt = line.rfind(">")
    return line[gt + 1:] if gt >= 0 else line


@dataclass
class _AutrContext:
    """Per-file audit-trail state (the reference's context map entry :579-731)."""

    autr_id_map: Dict[str, dict] = field(default_factory=dict)
    active_autr_id: Optional[str] = None
    active_log_id: Optional[str] = None
    active_alt_acct: Optional[str] = None
    elapsed_flag: bool = False
    sw_flag: bool = False
    active_service: Optional[str] = None
    service_map: Optional[Dict[str, List[dict]]] = None


@dataclass
class _SoapContext:
    log_id: str
    pull_next_value: bool = False


class _NativeRecordCache:
    """TTLCache-shaped facade over the native (logId, service) correlation
    map (native/parser.cpp) so read_line, tests, and cache_stats() see one
    coherent cache whether lines arrived via the batch fast path or the
    per-line reference path. Hit/miss/expiry semantics replicate TTLCache
    exactly (parity pinned by tests/test_parser_native_diff.py); the expiry
    callback fires from a drained batch instead of inline, which reorders
    only log lines, never records."""

    def __init__(self, engine, clock, on_expired_pair):
        self._e = engine
        self.clock = clock
        self._on_expired_pair = on_expired_pair
        self._server_ids: Dict[str, int] = {}
        self._server_names: List[str] = []

    def server_id(self, name: str) -> int:
        sid = self._server_ids.get(name)
        if sid is None:
            sid = len(self._server_names)
            self._server_ids[name] = sid
            self._server_names.append(name)
        return sid

    def server_name(self, sid: int) -> str:
        return self._server_names[sid]

    def _drain(self) -> None:
        if self._e.expired_pending():
            for lid, svc in self._e.drain_expired():
                self._on_expired_pair(
                    lid.decode("utf-8", "replace"), svc.decode("utf-8", "replace")
                )

    def park(self, log_id: str, service: str, server: str, start_ts: str) -> None:
        self._e.park(
            log_id.encode("utf-8", "replace"), service.encode("utf-8", "replace"),
            self.server_id(server), start_ts.encode("utf-8", "replace"),
            self.clock(),
        )
        self._drain()

    def take(self, log_id: str, service: str):
        """(server, start_ts) when found+popped, else None (key missing or
        service missing — _join_exit treats both as no-partial)."""
        r = self._e.take(
            log_id.encode("utf-8", "replace"), service.encode("utf-8", "replace"),
            self.clock(),
        )
        self._drain()
        if not r:  # None (no key) or () (key without this service)
            return None
        sid, ts = r
        return self.server_name(sid), ts.decode("utf-8", "replace")

    def get(self, key: str):
        """TTLCache.get view (counts a hit/miss, lazy-expires): the live
        service map as {service: {"server", "start_ts"}} — a COPY; parser
        internals mutate through park/take, not through this."""
        m = self._e.peek(key.encode("utf-8", "replace"), self.clock())
        self._drain()
        if m is None:
            return None
        return {
            svc.decode("utf-8", "replace"): {
                "server": self.server_name(sid),
                "start_ts": ts.decode("utf-8", "replace"),
            }
            for svc, (sid, ts) in m.items()
        }

    def sweep(self) -> None:
        self._e.sweep(self.clock())
        self._drain()

    def clear(self) -> None:
        self._e.clear()

    def stats(self) -> dict:
        keys, hits, misses = self._e.stats()
        return {"keys": keys, "hits": hits, "misses": misses}

    def __len__(self) -> int:
        return self._e.stats()[0]


class TransactionParser:
    """Stateful multi-file log parser. Feed lines via read_line(file_path, line);

    completed records arrive at ``on_record(tx, insert_to_db)``."""

    def __init__(
        self,
        on_record: Callable[[TxEntry, bool], None],
        *,
        logger=None,
        clock: Callable[[], float] = time.monotonic,
        server_from_path: Optional[Callable[[str], str]] = None,
        record_ttl_s: float = 120.0,
        need_num_ttl_s: float = 30.0,
        acct_ttl_s: float = 120.0,
        use_native: Optional[bool] = None,
        frame_sink: Optional[Callable[[bytes, int], None]] = None,
        frame_max_records: int = 512,
    ):
        self.on_record = on_record
        self.logger = logger
        # frame-emission mode (the zero-object byte spine): queue-bound
        # records skip TxEntry + on_record entirely — the finished CSV line
        # goes into a buffer that is packed into APF1 frame batches
        # (transport/frames.py) and handed to frame_sink(blob, n_records)
        # at chunk/sweep/drain boundaries or when frame_max_records
        # accumulate. db-direct records (insert_to_db=True) always keep the
        # per-record on_record path. APM_NO_FRAMES=1 kills the mode (the
        # APM_PARSE_NO_NATIVE pattern); frames OFF is the default wire.
        if os.environ.get("APM_NO_FRAMES", "") in ("1", "true"):
            frame_sink = None
        self.frame_sink = frame_sink
        self._frame_buf: list = []
        self._frame_max = max(1, int(frame_max_records))
        # carriage (APC1 trailer): per-record ingest stamps + the sampled
        # batch trace_id ride IN the frame, so e2e latency and trace
        # stitching survive fabrics that strip or never carry headers (the
        # pipelined shm-ring hop). APM_NO_FRAME_CARRIAGE=1 kills it —
        # frames then ship the bit-identical pre-carriage wire.
        self._frame_carriage = (
            frame_sink is not None
            and os.environ.get("APM_NO_FRAME_CARRIAGE", "") in ("", "0")
        )
        self._frame_ts: list = []  # per-record time.time(), parallel to _frame_buf
        # stage counters (ROADMAP "replay is parser-bound" quantification;
        # exported by obs.views.register_parser, surfaced by bench_replay):
        # plain dict ints — this is the per-line hot loop, registry
        # instruments stay out of it
        self.counters = {
            "lines_in": 0,      # raw lines through read_line/read_lines
            "tx_out": 0,        # complete TxEntry records emitted
            "db_direct_out": 0, # records routed straight to the DB queue
            "parse_ns": 0,      # wall ns inside _read_line / native chunks
            "native_lines": 0,  # lines that went through the native chunk path
            "prefilter_rejected": 0,  # lines the native pre-filter dropped
            "frames_emitted": 0,      # APF1 frame batches handed to frame_sink
            "frame_records_out": 0,   # records emitted via frames (no TxEntry)
        }
        self.server_from_path = server_from_path or (lambda fp: fp.split("/")[2] if len(fp.split("/")) > 2 else fp)
        # per-file dispatch cache: (kind, server, native server id) resolved
        # ONCE per file path, not per line — the filename classification and
        # server extraction are pure functions of the path, and read_line
        # runs at intake rates where two regex searches per line were ~15%
        # of the parser's whole budget
        self._file_info: Dict[str, tuple] = {}
        # per-file contexts: SOAP logId tracking + audit-trail state
        # machines. With the native engine BOTH live in C++ (the soap dict
        # is reached through the _soap_* accessors; app lines route through
        # the native machine even from read_line) so the batch and per-line
        # APIs share one state; these dicts serve the pure-Python path.
        self._soap_ctx: Dict[str, _SoapContext] = {}
        self._autr_ctx: Dict[str, _AutrContext] = {}
        self._file_ids: Dict[str, int] = {}
        self._clock = clock
        # trace plane (obs/trace): the parser is the raw-read ingest
        # boundary — read_lines notes each chunk's wall time so the producer
        # can anchor the sampled ingest span there. One attribute load +
        # integer compare per CHUNK (never per line); rate 0 = no-op.
        from ..obs.trace import get_tracer

        self._obs_tracer = get_tracer()
        # attribution plane (obs/attrib): the parser owns three stages —
        # the scan itself (mirrors parse_ns at chunk granularity), the
        # frame pack, and the cache-sweep timer. Cached clock references;
        # no-ops when the plane is off.
        from ..obs.attrib import (
            STAGE_FRAME_PACK,
            STAGE_PARSER_SCAN,
            STAGE_PARSER_SWEEP,
            get_attrib,
        )

        _att = get_attrib()
        self._att_scan = _att.clock(STAGE_PARSER_SCAN)
        self._att_pack = _att.clock(STAGE_FRAME_PACK)
        self._att_sweep = _att.clock(
            STAGE_PARSER_SWEEP, counters=("released", "failed", "examined")
        )
        self._neednum_released = 0  # numberless records handed on at expiry
        # One lock over the parser's state: the caches, the per-file
        # contexts, the frame buffer and the native engine. Held by
        # read_line, read_lines, sweep, drain and cache_stats, so the
        # cache-sweep timer and the tail pump threads never interleave with
        # a chunk; ctypes releases the GIL inside the native engine, which
        # the GIL alone would leave shared. Reentrant: read_lines without
        # the native engine feeds read_line. What the held sections call
        # out to (on_record -> the producer's write_line, frame_sink, the
        # logger) never calls back into the parser.
        self._lock = threading.RLock()
        # logId -> acctNum (backfill source)
        self.acct_cache = TTLCache(acct_ttl_s, clock=clock)
        # the native ingest fast path (marker pre-filter + field extraction
        # + correlation join in C++); None -> pure-Python reference path
        self._native = None
        if use_native if use_native is not None else (
            os.environ.get(_NO_NATIVE_ENV, "") not in ("1", "true")
        ):
            try:
                from ..native import ParserEngineNative

                self._native = ParserEngineNative(
                    record_ttl_s, max(record_ttl_s / 4, 1), clock()
                )
            except Exception:
                self._native = None  # no toolchain: Python fallback
        # logId -> {service: partial record}; expiry = no exit line found.
        # With the native engine the map lives in C++ (read_line and
        # read_lines share it through the park/take shims); the TTLCache
        # reference implementation is kept behind APM_PARSE_NO_NATIVE=1.
        if self._native is not None:
            self.record_cache = _NativeRecordCache(
                self._native, clock, self._on_partial_expired_pair
            )
        else:
            self.record_cache = TTLCache(record_ttl_s, clock=clock, on_expired=self._on_partial_expired)
        # logId -> {service: joined-but-numberless record}; expiry = emit
        # anyway. A key is set only on a miss (_park_need_num), never again
        # while live, so expiry hands records on in first-park order.
        self.need_num_cache = TTLCache(need_num_ttl_s, clock=clock, on_expired=self._on_neednum_expired)

    # -- cache expiry --------------------------------------------------------
    def _on_partial_expired(self, log_id: str, service_map: dict) -> None:
        for service, rec in service_map.items():
            self._on_partial_expired_pair(log_id, service)

    def _on_partial_expired_pair(self, log_id: str, service: str) -> None:
        if self.logger:
            self.logger.error(
                f"Partial record expired! No matching timing exit found. "
                f"Discarding. Service: {service} logId: {log_id}"
            )

    def _on_neednum_expired(self, log_id: str, need_map: dict) -> None:
        for service, rec in need_map.items():
            self._output(
                rec.get("server", ""), service, log_id,
                rec.get("alt_acct") or "",
                rec.get("start_ts", ""), rec["end_ts"], rec["elapsed"],
                rec.get("insert_to_db", False),
            )
            self._neednum_released += 1

    def _examined(self) -> int:
        """Deadlines the Python caches' sweeps have compared so far (the
        native record cache is not counted)."""
        return sum(
            getattr(c, "examined", 0)
            for c in (self.acct_cache, self.record_cache, self.need_num_cache)
        )

    def sweep(self) -> None:
        """The cache-sweep timer's body: expire the three caches (expired
        numberless records are handed on) and flush buffered frames. One
        ``parser_sweep`` span, taken once the parser lock is held; counts
        the records released, the deadlines compared and the sweeps that
        raised."""
        clk = self._att_sweep
        with self._lock:
            released0 = self._neednum_released
            examined0 = self._examined()
            try:
                with clk.span():
                    self.acct_cache.sweep()
                    self.record_cache.sweep()
                    self.need_num_cache.sweep()
                    if self._frame_buf:
                        self._flush_frames_safe("<sweep>")
            except Exception:
                clk.count("failed")
                raise
            finally:
                clk.count("released", self._neednum_released - released0)
                clk.count("examined", self._examined() - examined0)

    def drain(self) -> None:
        """End-of-replay: flush numberless records out, drop partials."""
        with self._lock:
            self.need_num_cache.flush_all()
            self.record_cache.clear()
            if self._frame_buf:
                self._flush_frames_safe("<drain>")

    # -- frame emission ------------------------------------------------------
    def flush_frames(self) -> None:
        """Pack buffered frame-mode lines into one APF1 batch — plus the
        carriage trailer (per-record ingest deltas off the batch's min
        stamp, and a head-sampled trace_id: one should_sample compare per
        BATCH, deterministic in the frames_emitted sequence) — and hand it
        to frame_sink. Called at chunk/sweep/drain boundaries and when the
        buffer reaches frame_max_records; a sink failure raises
        ConsumerError (batch dropped loudly, like a failed on_record)."""
        buf = self._frame_buf
        if not buf:
            return
        ts = self._frame_ts
        self._frame_buf = []
        self._frame_ts = []
        from ..transport import frames as _frames

        t0 = time.perf_counter()
        blob = _frames.encode_lines(buf)
        if self._frame_carriage and len(ts) == len(buf):
            base = min(ts)
            tr = self._obs_tracer
            seq = self.counters["frames_emitted"]
            trace_id = ""
            if tr.should_sample(seq):
                trace_id = f"tf-{os.getpid():x}-{seq}"
            blob = _frames.append_carriage(
                blob, base,
                [int((t - base) * 1000.0 + 0.5) for t in ts], trace_id,
            )
            if trace_id:
                # the batch's ingest span: raw-read anchor (chunk boundary)
                # -> packed and handed to the fabric
                tr.span(trace_id, "ingest", tr.ingest_start or base,
                        time.time(), records=len(buf))
        self._att_pack.add_busy(time.perf_counter() - t0)
        self.counters["frames_emitted"] += 1
        try:
            self.frame_sink(blob, len(buf))
        except Exception as e:
            raise ConsumerError(e) from e

    def _flush_frames_safe(self, where: str) -> None:
        try:
            self.flush_frames()
        except ConsumerError as e:
            if self.logger:
                self.logger.error(
                    f"Frame sink failed (batch dropped) at {where}: "
                    f"{e.__cause__!r}"
                )

    def cache_stats(self) -> dict:
        with self._lock:
            return {
                "acct": self.acct_cache.stats(),
                "record": self.record_cache.stats(),
                "need": self.need_num_cache.stats(),
            }

    # -- record emission -----------------------------------------------------
    def _output(self, server, service, log_id, acct_num, start_ts, end_ts, elapsed, insert_to_db=False):
        start_ms = convert_log_date_to_ms(start_ts)
        end_ms = convert_log_date_to_ms(end_ts)
        if "[" in service or "]" in service:
            # the sub/replace only fire on bracketed services; the gate
            # skips two regex passes on the (majority) plain names
            service = _PROVIDER_RE.sub("Provider:", service).replace("]", "")
        if not start_ms and end_ms:
            try:
                start_ms = str(int(end_ms) - int(elapsed))
            except (TypeError, ValueError):
                start_ms = ""
        top = "Y" if service.startswith("S:") else "N"  # == _TOPLEVEL_RE.match
        c = self.counters
        if self.frame_sink is not None and not insert_to_db:
            # frame mode, queue-bound record: format the CSV line directly
            # (format_tx_line == TxEntry(...).to_csv() byte for byte) and
            # buffer it for batch packing — no TxEntry, no on_record
            c["tx_out"] += 1
            c["frame_records_out"] += 1
            # start/end go in as OUR canonical str(int(...)) strings: the
            # _csv_num digit fast path renders them verbatim, which is the
            # same byte output the int(...) round trip produced ('' still
            # coerces to NaN; negatives and >15-digit strings take the full
            # js_parse_int route and agree with int()'s reading exactly)
            self._frame_buf.append(format_tx_line(
                server, service, log_id, acct_num, start_ms, end_ms,
                elapsed, top,
            ))
            if self._frame_carriage:
                self._frame_ts.append(time.time())
            if len(self._frame_buf) >= self._frame_max:
                self.flush_frames()
            return
        # start/end are OUR str(int(...)) strings (or ''): int() parses
        # them identically to js_parse_int, and TxEntry's int fast path
        # skips the per-field regex — '' stays '' and parses to NaN as
        # before. elapsed/acct_num come from the wild and keep the full
        # js_parse_int treatment inside TxEntry.
        tx = TxEntry(
            server, service, log_id, acct_num,
            int(start_ms) if start_ms else "",
            int(end_ms) if end_ms else "",
            elapsed, top,
        )
        c["tx_out"] += 1
        if insert_to_db:
            c["db_direct_out"] += 1
        try:
            self.on_record(tx, insert_to_db)
        except Exception as e:
            raise ConsumerError(e) from e

    # -- account numbers -----------------------------------------------------
    def _save_acct_num(self, acct_num: str, file_path: str, source: str, alt_log_id: Optional[str] = None):
        acct_num = acct_num.strip()
        if not _DIGITS_RE.match(acct_num):
            if self.logger:
                self.logger.error(f"Invalid acctNum (SRC={source}): {acct_num!r} from {file_path}")
            return
        if source == "bafmetainfo":
            log_id = alt_log_id
            if not log_id:
                return
        else:
            st = self._soap_state(file_path)
            if st is None:
                return
            log_id = st[0]
        self.acct_cache.set(log_id, acct_num)
        if source != "bafmetainfo":
            self._soap_close(file_path)
        self._backfill_need(log_id, acct_num, file_path)

    def _backfill_need(self, log_id: str, acct_num: str, file_path: str) -> None:
        """Release any parked numberless records for this logId."""
        need_map = self.need_num_cache.get(log_id)
        if need_map:
            server = self.server_from_path(file_path)
            for service in list(need_map):
                rec = need_map.pop(service)
                self._output(
                    rec.get("server") or server, service, log_id, acct_num,
                    rec.get("start_ts", ""), rec["end_ts"], rec["elapsed"],
                    rec.get("insert_to_db", False),
                )

    def _baf_meta_acct(self, line: str, file_path: str, log_id: str, tokens: List[str]) -> str:
        """Account number from BAF bracketed metadata, '' if absent (:486-497)."""
        if not _BAF_META_RE.search(line) or len(tokens) < 4:
            return ""
        info = re.sub(r".*]\[", "", tokens[3])
        info = _strip_brackets(info)
        acct = info.split(":")[-1]
        if acct:
            self._save_acct_num(acct, file_path, "bafmetainfo", log_id)
        return acct

    # -- SOAP ----------------------------------------------------------------
    # Context accessors: the per-file SOAP state lives in the native engine
    # when it is active (shared with the batch machine), else in _soap_ctx.
    def _soap_state(self, file_path: str):
        """(log_id, pull_next_value) of the open context, or None."""
        if self._native is not None:
            st = self._native.soap_get(self._file_info_for(file_path)[3])
            if st is None:
                return None
            return st[0].decode("utf-8", "replace"), st[1]
        ctx = self._soap_ctx.get(file_path)
        return None if ctx is None else (ctx.log_id, ctx.pull_next_value)

    def _soap_open(self, file_path: str, log_id: str) -> None:
        if self._native is not None:
            self._native.soap_set(
                self._file_info_for(file_path)[3],
                log_id.encode("utf-8", "replace"),
            )
        else:
            self._soap_ctx[file_path] = _SoapContext(log_id=log_id)

    def _soap_arm(self, file_path: str) -> None:
        if self._native is not None:
            self._native.soap_arm(self._file_info_for(file_path)[3])
        else:
            ctx = self._soap_ctx.get(file_path)
            if ctx is not None:
                ctx.pull_next_value = True

    def _soap_close(self, file_path: str) -> None:
        if self._native is not None:
            self._native.soap_close(self._file_info_for(file_path)[3])
        else:
            self._soap_ctx.pop(file_path, None)

    def _parse_soap(self, line: str, file_path: str) -> None:
        if _SOAP_IN_RE.match(line):
            token = line.split()[1]
            self._soap_open(file_path, token.split("=")[1])
        elif _SOAP_OUT_RE.match(line):
            self._soap_close(file_path)
        else:
            st = self._soap_state(file_path)
            if st is None:
                return
            if _SOAP_ACCT_RE.search(line):
                self._save_acct_num(re.split(r"<|>", line.strip())[2], file_path, "standard")
            elif _SOAP_ALT_KEY_RE.search(line):
                self._soap_arm(file_path)
            elif _SOAP_ALT_VALUE_RE.search(line) and st[1]:
                self._save_acct_num(re.split(r"<|>", line.strip())[2], file_path, "riskStrategy")

    # -- CommonTiming (EJB + standard) --------------------------------------
    # Record-cache access goes through park/take so the reference handlers
    # and the native event loop share ONE map regardless of backend. The
    # TTLCache branch reproduces the original inline get/set/pop sequence
    # byte-for-byte (incl. hit/miss accounting); the native branch defers to
    # the C++ map with identical semantics.
    def _park_partial(self, log_id: str, service: str, server: str, start_ts: str) -> None:
        rc = self.record_cache
        if self._native is not None:
            rc.park(log_id, service, server, start_ts)
            return
        smap = rc.get(log_id)
        if smap is None:
            smap = {}
            rc.set(log_id, smap)
        smap[service] = {"server": server, "start_ts": start_ts}

    def _take_partial(self, log_id: str, service: str):
        """(server, start_ts) of the parked partial — popped — or None."""
        rc = self.record_cache
        if self._native is not None:
            return rc.take(log_id, service)
        smap = rc.get(log_id)
        partial = smap.get(service) if smap else None
        if partial is None:
            return None
        smap.pop(service, None)
        return partial["server"], partial["start_ts"]

    def _join_exit(self, line, file_path, log_id, service, server, end_ts, elapsed, tokens, salvage: bool):
        partial = self._take_partial(log_id, service)
        if partial is None:
            if self.logger:
                self.logger.error(
                    f"CommonTiming exit had no matching entry in the record cache. "
                    f"logId: {log_id} service: {service}"
                )
            if salvage:
                acct = self._baf_meta_acct(line, file_path, log_id, tokens)
                self._output(server, service, "", acct, "", end_ts, elapsed)
            else:
                self._output(server, service, "", "", "", end_ts, elapsed)
            return
        p_server, p_start_ts = partial
        acct = self.acct_cache.get(log_id)
        if acct:
            self._output(server, service, log_id, acct, p_start_ts, end_ts, elapsed)
        else:
            alt = self._baf_meta_acct(line, file_path, log_id, tokens) if salvage else ""
            self._park_need_num(
                log_id, service, p_server, p_start_ts, end_ts, elapsed, alt
            )

    def _park_need_num(self, log_id, service, server, start_ts, end_ts, elapsed,
                       alt_acct, insert_to_db=None) -> None:
        need = self.need_num_cache.get(log_id)
        if need is None:
            need = {}
            self.need_num_cache.set(log_id, need)
        rec = {
            "server": server, "start_ts": start_ts,
            "end_ts": end_ts, "elapsed": elapsed, "alt_acct": alt_acct,
        }
        if insert_to_db is not None:
            rec["insert_to_db"] = insert_to_db
        need[service] = rec

    def _parse_ejb_entry(self, line: str, server: str) -> None:
        arr = line.split()
        log_id = _strip_brackets(arr[0])
        if not log_id:
            return
        self._park_partial(log_id, f"S:{arr[13]}", server, f"{arr[1]} {arr[2]}")

    def _parse_ejb_exit(self, line: str, file_path: str, server: str) -> None:
        arr = line.split()
        log_id = _strip_brackets(arr[0])
        end_ts = f"{arr[1]} {arr[2]}"
        service = f"S:{arr[9]}"
        elapsed = arr[11]
        if not log_id:
            self._output(server, service, "", "", "", end_ts, elapsed)
            return
        self._join_exit(line, file_path, log_id, service, server, end_ts, elapsed, arr, salvage=False)

    def _parse_ct_entry(self, line: str, server: str) -> None:
        arr = line.split()
        log_id = _strip_brackets(arr[0])
        if not log_id:
            return
        # split on INFO: BAF logs interleave bracketed metadata that breaks
        # positional token counts (:459)
        half = line.split("INFO", 1)[1].strip().split()
        self._park_partial(log_id, half[1], server, f"{arr[1]} {arr[2]}")

    def _parse_ct_exit(self, line: str, file_path: str, server: str) -> None:
        arr = line.split()
        half = line.split("INFO", 1)[1].strip().split()
        log_id = _strip_brackets(arr[0])
        end_ts = f"{arr[1]} {arr[2]}"
        service, elapsed = half[1], half[5]
        if not log_id:
            acct = self._baf_meta_acct(line, file_path, log_id, arr)
            self._output(server, service, "", acct, "", end_ts, elapsed)
            return
        self._join_exit(line, file_path, log_id, service, server, end_ts, elapsed, arr, salvage=True)

    # -- audit trail ---------------------------------------------------------
    def _parse_app_line(self, line: str, file_path: str, server: str) -> None:
        if _AUTR_MAP_RE.search(line):
            arr = line.split()
            log_id = _strip_brackets(arr[0])
            autr_id = arr[5].split("=")[1]
            ctx = self._autr_ctx.setdefault(file_path, _AutrContext())
            alt = self._baf_meta_acct(line, file_path, log_id, arr)
            ctx.autr_id_map[autr_id] = {"log_id": log_id, "alt_acct": alt}
            return
        if _AUTR_LINE_RE.match(line):
            ctx = self._autr_ctx.get(file_path)
            if ctx is None:
                if self.logger:
                    self.logger.error("Missing context for audit trail id line (startup race)")
                return
            autr_id = line.split(":")[1].strip()
            mapping = ctx.autr_id_map.pop(autr_id, None)
            if mapping is None or not mapping.get("log_id"):
                if self.logger:
                    self.logger.error(f"Could not resolve autrId {autr_id} to a logId")
                return
            ctx.service_map = {}
            ctx.active_autr_id = autr_id
            ctx.active_log_id = mapping["log_id"]
            ctx.active_alt_acct = mapping.get("alt_acct")
            ctx.elapsed_flag = False
            ctx.sw_flag = False
            ctx.active_service = None
            return

        ctx = self._autr_ctx.get(file_path)
        if ctx is None or not ctx.active_log_id:
            return  # random log line

        if _ELAPSED_START_RE.search(line):
            ctx.elapsed_flag = True
        elif ctx.elapsed_flag:
            if _ELAPSED_END_RE.match(line):
                ctx.elapsed_flag = False
            else:
                arr = line.split(":")
                service = arr[0].strip()
                elapsed = _strip_brackets(arr[1].split()[0])
                ctx.service_map.setdefault(service, []).append({"elapsed": elapsed})
        elif _SW_XML_START_RE.search(line):
            ctx.sw_flag = True
        elif ctx.sw_flag:
            if _SW_XML_END_RE.search(line):
                ctx.active_autr_id = None
                ctx.active_log_id = None
                ctx.active_alt_acct = None
                ctx.elapsed_flag = False
                ctx.sw_flag = False
                ctx.active_service = None
                ctx.service_map = None
            elif _SW_NAME_RE.search(line):
                ctx.active_service = _xml_text(line)
            elif ctx.active_service:
                if _SW_START_RE.search(line):
                    entries = ctx.service_map.get(ctx.active_service)
                    if not entries:
                        if self.logger:
                            self.logger.error(
                                f"No serviceMap entry for {ctx.active_service} on startTime"
                            )
                        return
                    entries[0]["start_ts"] = _xml_text(line)
                elif _SW_STOP_RE.search(line):
                    end_ts = _xml_text(line)
                    service = ctx.active_service
                    entries = ctx.service_map.get(service)
                    if not entries:
                        if self.logger:
                            self.logger.error(f"No serviceMap entry for {service} on stopTime")
                        return
                    rec = entries.pop(0)
                    log_id = ctx.active_log_id
                    acct = self.acct_cache.get(log_id)
                    # non-Provider audit records bypass stats straight to DB (:697)
                    insert_to_db = not _PROVIDER_RE.search(service)
                    if acct:
                        self._output(
                            server, service, log_id, acct,
                            rec.get("start_ts", ""), end_ts, rec["elapsed"], insert_to_db,
                        )
                    else:
                        self._park_need_num(
                            log_id, service, server, rec.get("start_ts", ""),
                            end_ts, rec["elapsed"], ctx.active_alt_acct,
                            insert_to_db,
                        )

    # -- dispatch ------------------------------------------------------------
    def read_line(self, file_path: str, line: str) -> None:
        """Per-line dispatch; malformed lines are logged and skipped, never

        fatal (JS's out-of-range indexing yields undefined where Python would
        raise — fail-open is the equivalent robustness)."""
        with self._lock:
            c = self.counters
            c["lines_in"] += 1
            t0 = time.perf_counter_ns()
            try:
                self._read_line(file_path, line)
            except ConsumerError as e:
                # downstream (engine/sink) failure, not bad input — surface loudly
                if self.logger:
                    self.logger.error(
                        f"Record consumer failed (record dropped) in {file_path}: "
                        f"{e.__cause__!r}: {line[:200]!r}"
                    )
            except Exception as e:
                if self.logger:
                    self.logger.error(f"Unparseable log line in {file_path}: {e}: {line[:200]!r}")
            finally:
                dt = time.perf_counter_ns() - t0
                c["parse_ns"] += dt
                self._att_scan.add_busy(dt * 1e-9)

    # -- batch API (native ingest fast path) ---------------------------------
    def read_lines(self, file_path: str, data: Union[bytes, str]) -> int:
        """Feed a chunk of complete '\\n'-separated lines from one file.

        The batch counterpart of read_line and the parser's hot path: with
        the native engine the chunk takes ONE pass through C++ (marker
        pre-filter + field extraction + correlation join) and only
        marker-relevant lines ever become Python objects; without it (no
        toolchain, or APM_PARSE_NO_NATIVE=1) the chunk degrades to the
        per-line reference loop. Both produce bit-identical TxEntry streams
        and cache statistics. A trailing newline terminates the last line
        (no empty final line); interior empty lines count as (no-op) lines,
        matching read_line('') semantics. Returns the number of lines.
        """
        if isinstance(data, str):
            data = data.encode("utf-8", "replace")
        if not data:
            return 0
        with self._lock:
            return self._read_lines_locked(file_path, data)

    def _read_lines_locked(self, file_path: str, data: bytes) -> int:
        self._obs_tracer.note_ingest_start()  # chunk-granular ingest anchor
        if self._native is None:
            segs = data.decode("utf-8", "replace").split("\n")
            if segs[-1] == "" and len(segs) > 1:
                segs.pop()
            for line in segs:
                self.read_line(file_path, line)
            if self._frame_buf:
                self._flush_frames_safe(file_path)
            return len(segs)
        c = self.counters
        t0 = time.perf_counter_ns()
        try:
            with self._att_scan.span():
                return self._read_lines_native(file_path, data)
        finally:
            c["parse_ns"] += time.perf_counter_ns() - t0
            if self._frame_buf:
                self._flush_frames_safe(file_path)

    def _read_lines_native(self, file_path: str, data: bytes) -> int:
        info = self._file_info_for(file_path)
        c = self.counters
        before = c["lines_in"]
        off = 0
        while off < len(data):
            # a RAW barrier stops the native scan mid-chunk so the Python
            # replay runs in strict line order against the shared state;
            # re-invoke on the remainder (rare: exotic/malformed lines only)
            consumed = self._native_chunk(
                file_path, info, data[off:] if off else data, count=True
            )
            off += consumed
        return c["lines_in"] - before

    def _native_chunk(self, file_path: str, info, data: bytes,
                      count: bool = False) -> int:
        """One native scan pass; processes its events. Returns bytes
        consumed (== len(data) unless a RAW barrier stopped the scan)."""
        kind, server, sid, fid = info
        eng = self._native
        ev, pool, counts = eng.chunk(data, kind, sid, fid, self._clock())
        if count:
            c = self.counters
            c["lines_in"] += counts[0]
            c["native_lines"] += counts[0]
            c["prefilter_rejected"] += counts[1]
        if eng.expired_pending():
            self.record_cache._drain()
        consumed = counts[5]
        if not len(ev):
            return consumed

        # span decode: off >= 0 -> chunk buffer, off < 0 -> pool. Every
        # non-RAW span is pure ASCII (exotic lines are routed RAW), so one
        # latin-1 decode of the whole chunk up front — 1:1 bytes->chars,
        # byte offsets stay valid — and plain str slicing per span replace
        # a bytes-slice + decode pair per field; for ASCII spans the result
        # equals the reference's errors='replace' slicing exactly.
        dstr = data.decode("latin-1")
        pstr = pool.decode("latin-1")

        def sp(off, ln):
            if off >= 0:
                return dstr[off: off + ln]
            s = -off - 1
            return pstr[s: s + ln]

        CLS_EJB_EXIT = eng.CLS_EJB_EXIT
        CLS_CT_EXIT = eng.CLS_CT_EXIT
        CLS_AUDIT_STOP = eng.CLS_AUDIT_STOP
        # field indexes into the event row (EVENT dtype order); rows are
        # indexed selectively per class — the hot classes touch a handful
        # of fields and a full 19-name unpack per event is measurable here
        for row in ev.tolist():
            cls = row[2]
            try:
                if cls == CLS_EJB_EXIT or cls == CLS_CT_EXIT:
                    baf_len = row[16]
                    self._exit_event(
                        file_path, server, cls == CLS_CT_EXIT, row[3],
                        sp(row[4], row[5]) if row[5] >= 0 else "",
                        sp(row[6], row[7]), sp(row[8], row[9]),
                        sp(row[10], row[11]),
                        row[14], sp(row[12], row[13]) if row[13] >= 0 else "",
                        sp(row[15], baf_len) if baf_len >= 0 else None,
                    )
                elif cls == CLS_AUDIT_STOP:
                    self._audit_stop_event(
                        server, sp(row[8], row[9]), sp(row[4], row[5]),
                        sp(row[6], row[7]), sp(row[12], row[13]),
                        sp(row[10], row[11]), sp(row[15], row[16]),
                        bool(row[3] & 16),  # FL_INSERT_DB
                    )
                elif cls == 12 or cls == 14:  # SOAP_ACCT / SOAP_ALT_VALUE
                    self._save_acct_event(
                        sp(row[6], row[7]), file_path, sp(row[4], row[5]),
                        "standard" if cls == 12 else "riskStrategy",
                    )
                elif cls == 21:  # CLS_ACCT_SAVE_BAF (audit map line)
                    self._save_acct_num(
                        sp(row[6], row[7]), file_path, "bafmetainfo",
                        sp(row[4], row[5]),
                    )
                elif cls == 23:  # CLS_AUDIT_LOG
                    self._audit_log_event(
                        row[17], sp(row[8], row[9]) if row[9] >= 0 else "",
                        file_path, data, row[0], row[1],
                    )
                else:  # CLS_RAW
                    # exotic / malformed line: the reference handler decides
                    # (record/soap state reached through the backend shims)
                    self._read_line_ref(
                        file_path,
                        data[row[0]: row[0] + row[1]].decode("utf-8", "replace"),
                    )
            except ConsumerError as e:
                if self.logger:
                    line = data[row[0]: row[0] + row[1]].decode("utf-8", "replace")
                    self.logger.error(
                        f"Record consumer failed (record dropped) in {file_path}: "
                        f"{e.__cause__!r}: {line[:200]!r}"
                    )
            except Exception as e:
                if self.logger:
                    line = data[row[0]: row[0] + row[1]].decode("utf-8", "replace")
                    self.logger.error(
                        f"Unparseable log line in {file_path}: {e}: {line[:200]!r}"
                    )
        return consumed

    def _save_acct_event(self, acct_num: str, file_path: str, log_id: str,
                         source: str) -> None:
        """_save_acct_num's SOAP tail with the context logId captured at
        scan time (the native machine already closed the context on a
        digits-valid number, exactly where the reference pops it)."""
        acct_num = acct_num.strip()
        if not _DIGITS_RE.match(acct_num):
            if self.logger:
                self.logger.error(f"Invalid acctNum (SRC={source}): {acct_num!r} from {file_path}")
            return
        self.acct_cache.set(log_id, acct_num)
        self._backfill_need(log_id, acct_num, file_path)

    def _audit_stop_event(self, server, service, log_id, start_ts, end_ts,
                          elapsed, alt_acct, insert_to_db: bool) -> None:
        """The stopTime emission tail of _parse_app_line (the state machine
        itself ran natively)."""
        acct = self.acct_cache.get(log_id)
        if acct:
            self._output(server, service, log_id, acct, start_ts, end_ts,
                         elapsed, insert_to_db)
        else:
            self._park_need_num(log_id, service, server, start_ts, end_ts,
                                elapsed, alt_acct, insert_to_db)

    def _audit_log_event(self, code: int, detail: str, file_path: str,
                         data: bytes, line_off: int, line_len: int) -> None:
        """Reference log lines whose branches ran natively (log text parity;
        no record/state effect)."""
        if not self.logger:
            return
        if code == 1:
            self.logger.error("Missing context for audit trail id line (startup race)")
        elif code == 2:
            self.logger.error(f"Could not resolve autrId {detail} to a logId")
        elif code == 3:
            self.logger.error(f"No serviceMap entry for {detail} on startTime")
        elif code == 4:
            self.logger.error(f"No serviceMap entry for {detail} on stopTime")
        elif code == 5:
            line = data[line_off: line_off + line_len].decode("utf-8", "replace")
            self.logger.error(
                f"Unparseable log line in {file_path}: list index out of range: {line[:200]!r}"
            )

    def _baf_salvage(self, flags: int, tok3: Optional[str], file_path: str,
                     log_id: str) -> str:
        """_baf_meta_acct with the regex gate + tokens[3] precomputed
        natively (FL_BAF iff _BAF_META_RE matched and len(tokens) >= 4)."""
        if not (flags & self._native.FL_BAF) or tok3 is None:
            return ""
        info = re.sub(r".*]\[", "", tok3)
        info = _strip_brackets(info)
        acct = info.split(":")[-1]
        if acct:
            self._save_acct_num(acct, file_path, "bafmetainfo", log_id)
        return acct

    def _exit_event(self, file_path, server, salvage, flags, log_id, end_ts,
                    service, elapsed, jserver, jts, baf_tok) -> None:
        """_parse_ejb_exit/_parse_ct_exit + _join_exit with extraction AND
        the record-cache take already done natively (keep in lockstep with
        those handlers — parity pinned by test_parser_native_diff)."""
        eng = self._native
        if flags & eng.FL_LOGID_EMPTY:
            acct = self._baf_salvage(flags, baf_tok, file_path, "") if salvage else ""
            self._output(server, service, "", acct, "", end_ts, elapsed)
            return
        if not (flags & eng.FL_JOIN_FOUND):
            if self.logger:
                self.logger.error(
                    f"CommonTiming exit had no matching entry in the record cache. "
                    f"logId: {log_id} service: {service}"
                )
            if salvage:
                acct = self._baf_salvage(flags, baf_tok, file_path, log_id)
                self._output(server, service, "", acct, "", end_ts, elapsed)
            else:
                self._output(server, service, "", "", "", end_ts, elapsed)
            return
        p_server = self.record_cache.server_name(jserver)
        acct = self.acct_cache.get(log_id)
        if acct:
            self._output(server, service, log_id, acct, jts, end_ts, elapsed)
        else:
            alt = self._baf_salvage(flags, baf_tok, file_path, log_id) if salvage else ""
            self._park_need_num(log_id, service, p_server, jts, end_ts, elapsed, alt)

    def _file_info_for(self, file_path: str) -> tuple:
        info = self._file_info.get(file_path)
        if info is None:
            name = file_path.rsplit("/", 1)[-1]
            kind = (
                0 if _SOAP_FILE_RE.search(name)
                else 1 if _SERVER_FILE_RE.search(name)
                else 2
            )
            server = self.server_from_path(file_path)
            if self._native is not None:
                sid = self.record_cache.server_id(server)
                fid = self._file_ids.setdefault(file_path, len(self._file_ids))
            else:
                sid = fid = -1
            info = (kind, server, sid, fid)
            self._file_info[file_path] = info
        return info

    def _read_line(self, file_path: str, line: str) -> None:
        if not line:
            return
        info = self._file_info_for(file_path)
        if self._native is not None and info[0] == 2:
            # app-log lines must run through the native audit machine even
            # on the per-line API — its state lives in C++ and cannot be
            # split with the Python reference context
            data = line.encode("utf-8", "replace")
            off = 0
            while off < len(data):
                consumed = self._native_chunk(
                    file_path, info, data[off:] if off else data
                )
                off += consumed
            return
        self._read_line_ref(file_path, line, info)

    def _read_line_ref(self, file_path: str, line: str, info=None) -> None:
        """The reference per-line dispatch (also the RAW-event replay path;
        record/soap state reached through the backend shims)."""
        kind, server = (info or self._file_info_for(file_path))[:2]

        if kind == 0:
            self._parse_soap(line, file_path)
            return
        m = _SERVER_DISPATCH_RE.search(line)
        if kind == 1:  # server.log: EJB + standard CommonTiming forms
            if m is None:
                return
            # the reference's sequential ladder priority, reconstructed from
            # the pre-filter match itself: lastindex is the winning marker at
            # the LEFTMOST occurrence; a higher-priority marker can only beat
            # it by occurring strictly later in the line, so only the
            # patterns above lastindex are (rarely) re-searched — the common
            # single-marker line dispatches with zero extra regex work.
            j = m.lastindex
            if j > 1:
                p = m.start() + 1
                for i in range(1, j):
                    if _LADDER_RES[i - 1].search(line, p):
                        j = i
                        break
            if j == 1:
                self._parse_ejb_entry(line, server)
            elif j == 2:
                self._parse_ejb_exit(line, file_path, server)
            elif j == 3:
                self._parse_ct_entry(line, server)
            else:
                self._parse_ct_exit(line, file_path, server)
        else:  # APP log: CT forms only; EJB markers fall through to app state
            if m is not None and (
                m.lastindex == 3 or _CT_ENTRY_RE.search(line, m.start() + 1)
            ):
                self._parse_ct_entry(line, server)
            elif m is not None and (
                m.lastindex == 4 or _CT_EXIT_RE.search(line, m.start() + 1)
            ):
                self._parse_ct_exit(line, file_path, server)
            else:
                self._parse_app_line(line, file_path, server)
