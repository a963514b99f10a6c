"""Batched DB sink.

Role parity with the reference's terminal stage (stream_insert_db.js):

- per-entry-type buffers keyed by the 2-char tag (``tx``/``fs``/``al``/``jx``;
  plain ``st`` entries are rejected just like consumeMsg does,
  stream_insert_db.js:355-376),
- flush when a buffer reaches ``dbInsertBufferLimit`` records or when
  ``dbMaxTimeBetweenInsertsMs`` elapses since the first record entered an
  empty buffer (stream_insert_db.js:329-353; config/apm_config.json:230-231),
- one multi-row INSERT per flush (the pg-promise ``helpers.insert`` role,
  stream_insert_db.js:298-302),
- on insert failure the drained rows are pushed back onto the FRONT of the
  live buffer — ahead of anything that arrived during the attempt — giving the
  same retry-forever, order-preserving semantics as the unshift loop at
  stream_insert_db.js:310-320,
- un-inserted buffers survive restarts via a JSON resume file
  (stream_insert_db.js:166, 225; SURVEY.md §5.4).

The executor is pluggable: a fake (in-memory, for tests — the seam the
reference never had), SQLite (stdlib, always available), or Postgres (gated on
a driver being installed; the reference's production target). Executors take
insert tuples in column order (``insert_rows``). A tx line is buffered as it
arrived and turned into its tuple at flush, with no Entry, dict or datetime
per row; other rows are dicts, adapted column by column (datetime -> ISO-8601,
dict -> JSON for the jsonb columns).
"""

from __future__ import annotations

import json
import math
import threading
import time
from datetime import datetime, timezone
from itertools import groupby
from operator import itemgetter
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..entries import Entry, EntryFactory, js_parse_int
from ..utils.counters import DBStats
from ..utils.resume import load_resume_file, save_resume_file


class ColumnSet:
    """Table name + ordered column list for one entry type
    (pg-promise ColumnSet role, stream_insert_db.js:149-160)."""

    def __init__(self, table: str, columns: Sequence[str]):
        self.table = table
        self.columns = tuple(columns)


def column_sets_from_config(db_config: dict) -> Dict[str, ColumnSet]:
    """The four column sets of getColumnSets (stream_insert_db.js:149-160)."""
    return {
        "tx": ColumnSet(
            db_config.get("dbTxTable", "tx"),
            ("endts", "startts", "server", "service", "logid", "acctnum", "elapsed", "toplevel"),
        ),
        "fs": ColumnSet(
            db_config.get("dbStatTable", "stats"),
            ("timestamp", "server", "service", "tpm", "lag", "stats"),
        ),
        "al": ColumnSet(
            db_config.get("dbAlertTable", "alerts"),
            ("entrytimestamp", "alerttimestamp", "server", "service", "cause", "entry"),
        ),
        "jx": ColumnSet(
            db_config.get("dbJmxTable", "jmx"),
            (
                "timestamp", "server", "dsinusenodes", "dsactivenodes", "dsavailablenodes",
                "heapused", "heapcommitted", "heapmax", "metaused", "metacommitted",
                "metamax", "sysload", "classcnt", "threadcnt", "daemonthreadcnt",
                "beanpoolavailablecnt", "beanpoolcurrentsize", "beanpoolmaxsize",
            ),
        ),
    }


def _iso_z(dt: datetime) -> str:
    return dt.astimezone(timezone.utc).isoformat(timespec="milliseconds").replace("+00:00", "Z")


def _json_datetime_only(o):
    """json.dumps default for jsonb columns: datetimes serialize like
    JSON.stringify'd Dates; anything else stays a LOUD TypeError so corrupt
    objects fail the flush (and re-queue) instead of persisting as reprs."""
    if isinstance(o, datetime):
        return _iso_z(o)
    raise TypeError(f"Object of type {type(o).__name__} is not JSON serializable")


def _adapt(value):
    """Common scalar adaptation: datetime -> ISO-8601 Z (JS Date.toJSON shape),
    dict -> compact JSON (jsonb columns), NaN -> None. Nested dicts may carry
    datetimes of their own (AlertEntry embeds the full triggering entry,
    entries.js:210) — they serialize to the same ISO-Z shape JSON.stringify
    gives a Date."""
    if isinstance(value, datetime):
        return _iso_z(value)
    if isinstance(value, dict):
        return json.dumps(
            value, separators=(",", ":"), allow_nan=False,
            default=_json_datetime_only,
        )
    if isinstance(value, float) and math.isnan(value):
        return None
    return value


def adapt_rows(cs: ColumnSet, rows: List[dict]) -> List[tuple]:
    """Dict rows -> insert tuples in ``cs`` column order."""
    return [tuple(_adapt(r.get(c)) for c in cs.columns) for r in rows]


# the tx wire fields (tx|server|service|logid|acctnum|startts|endts|elapsed|
# toplevel) in the tx column order (endts, startts, server, service, logid,
# acctnum, elapsed, toplevel)
_TX_FIELDS = itemgetter(6, 5, 1, 2, 3, 4, 7, 8)

# Below 2**33 s (year 2242) the double nearest ms / 1000.0 is within half a
# microsecond of the exact value, so fromtimestamp rounds it back to the
# exact millisecond: the ISO string is the whole second's plus ``ms % 1000``.
_EXACT_MS = (1 << 33) * 1000

# whole seconds held in a writer's ISO-prefix cache before it starts over
_ISO_PREFIX_CACHE = 4096
_MS_SUFFIX = tuple(f"{ms:03d}Z" for ms in range(1000))

_LINE_ERRORS = (IndexError, ValueError, OverflowError, OSError)


def _is_line(row) -> bool:
    return type(row) is str


def _tx_int(field: str) -> Optional[int]:
    """acctnum/elapsed column value: ``int(js_parse_int(field))``, NaN -> None."""
    if len(field) < 16 and field.isascii() and field.isdigit():
        return int(field)  # under 2**53: the float round trip is exact
    if field == "NaN":
        return None
    v = js_parse_int(field)
    return None if v != v else int(v)


class Executor:
    """The executors' shared surface: ``insert_rows`` takes insert tuples in
    column order; ``insert_many`` adapts dict rows to them first."""

    def insert_rows(self, cs: ColumnSet, values: List[tuple]) -> None:
        raise NotImplementedError

    def insert_many(self, cs: ColumnSet, rows: List[dict]) -> None:
        self.insert_rows(cs, adapt_rows(cs, rows))


class FakeExecutor(Executor):
    """In-memory executor for tests: records every batch; can be told to fail."""

    def __init__(self):
        self.tables: Dict[str, List[tuple]] = {}
        self.batches: List[Tuple[str, int]] = []
        self.scripts: List[str] = []
        self.fail = False

    def insert_rows(self, cs: ColumnSet, values: List[tuple]) -> None:
        if self.fail:
            raise RuntimeError("injected insert failure")
        self.tables.setdefault(cs.table, []).extend(values)
        self.batches.append((cs.table, len(values)))

    def execute_script(self, sql: str) -> None:
        self.scripts.append(sql)

    def close(self) -> None:
        pass


class SQLiteExecutor(Executor):
    """SQLite executor (stdlib). Tables are created on demand with TEXT-affinity
    columns — SQLite's dynamic typing keeps numerics numeric."""

    def __init__(self, path: str = ":memory:"):
        import sqlite3

        # The writer may flush from its timer thread while the consumer thread
        # adds rows; a single connection guarded by the writer's buffer lock
        # would serialize anyway, but check_same_thread must be off for the
        # timer-thread flush path.
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._created: set = set()
        self._lock = threading.Lock()

    def insert_rows(self, cs: ColumnSet, values: List[tuple]) -> None:
        cols = ", ".join(cs.columns)
        ph = ", ".join("?" for _ in cs.columns)
        with self._lock:
            if cs.table not in self._created:
                self._conn.execute(f"CREATE TABLE IF NOT EXISTS {cs.table} ({cols})")
                self._created.add(cs.table)
            self._conn.executemany(f"INSERT INTO {cs.table} ({cols}) VALUES ({ph})", values)
            self._conn.commit()

    def execute_script(self, sql: str) -> None:
        """Run provisioning DDL (tools/schema.py) on this backend."""
        with self._lock:
            self._conn.executescript(sql)
            self._conn.commit()

    def close(self) -> None:
        with self._lock:
            self._conn.close()


class PostgresExecutor(Executor):  # pragma: no cover - requires a driver + live server
    """Postgres executor (the production target, stream_insert_db.js:133-143).

    Gated: constructed only when a driver (psycopg2 or pg8000) is importable.
    One multi-row INSERT per call, matching the pg-promise helpers.insert path.
    """

    def __init__(self, *, user: str, host: str, database: str, password: Optional[str] = None, port: int = 5432):
        driver = None
        try:
            import psycopg2  # type: ignore

            driver = "psycopg2"
            self._conn = psycopg2.connect(
                user=user, host=host, dbname=database, password=password, port=port
            )
        except ImportError:
            try:
                import pg8000.native  # type: ignore

                driver = "pg8000"
                self._conn = pg8000.native.Connection(
                    user, host=host, database=database, password=password, port=port
                )
            except ImportError:
                raise RuntimeError(
                    "No Postgres driver available (psycopg2/pg8000); "
                    "use dbBackend 'sqlite' or 'fake'"
                )
        self._driver = driver
        self._lock = threading.Lock()

    def insert_rows(self, cs: ColumnSet, values: List[tuple]) -> None:
        cols = ", ".join(cs.columns)
        with self._lock:
            if self._driver == "psycopg2":
                ph = ", ".join("%s" for _ in cs.columns)
                with self._conn.cursor() as cur:
                    cur.executemany(f"INSERT INTO {cs.table} ({cols}) VALUES ({ph})", values)
                self._conn.commit()
            else:
                ph = ", ".join(f":p{i}" for i in range(len(cs.columns)))
                for row in values:
                    self._conn.run(
                        f"INSERT INTO {cs.table} ({cols}) VALUES ({ph})",
                        **{f"p{i}": v for i, v in enumerate(row)},
                    )

    def execute_script(self, sql: str) -> None:
        """Run provisioning DDL (tools/schema.py); driver differences stay here."""
        with self._lock:
            if self._driver == "psycopg2":
                with self._conn.cursor() as cur:
                    cur.execute(sql)
                self._conn.commit()
            else:  # pg8000.native: one statement per run()
                for stmt in sql.split(";"):
                    if stmt.strip():
                        self._conn.run(stmt)

    def close(self) -> None:
        self._conn.close()


def make_executor(db_config: dict):
    """Executor from config ``dbBackend``: fake | sqlite | postgres."""
    backend = db_config.get("dbBackend", "fake")
    if backend == "fake":
        return FakeExecutor()
    if backend == "sqlite":
        return SQLiteExecutor(db_config.get("dbFileFullPath", ":memory:"))
    if backend == "postgres":
        return PostgresExecutor(
            user=db_config.get("dbUser", "prod"),
            host=db_config.get("dbHost", "localhost"),
            database=db_config.get("dbDatabase", "apm"),
            password=db_config.get("dbPassword"),
            port=int(db_config.get("dbPort", 5432)),
        )
    raise ValueError(f"Unknown dbBackend: {backend!r}")


class DBWriter:
    """Per-type buffering + batch flush around a pluggable executor.

    Thread model: ``add``/``consume_line`` may be called from a consumer
    thread while the flush timer fires on the writer's own daemon thread; a
    single lock guards the buffers, and flushes drain to a temp list first so
    concurrent adds never interleave into a half-written batch (the async race
    the reference comments on at stream_insert_db.js:288-301).
    """

    REJECTED_TYPES = ("st",)

    def __init__(
        self,
        executor,
        db_config: dict,
        *,
        db_stats: Optional[DBStats] = None,
        logger=None,
        clock: Callable[[], float] = time.monotonic,
        start_timer: bool = True,
    ):
        self.executor = executor
        self.column_sets = column_sets_from_config(db_config)
        self.buffer_limit = int(db_config.get("dbInsertBufferLimit", 1000))
        self.max_ms = float(db_config.get("dbMaxTimeBetweenInsertsMs", 5000))
        self.db_stats = db_stats
        self.logger = logger
        self.clock = clock
        self._factory = EntryFactory()
        self._lock = threading.RLock()
        # dict rows, and in the tx buffer also the tx wire lines themselves
        # (converted to insert tuples at flush, _line_values)
        self._buffers: Dict[str, List] = {t: [] for t in self.column_sets}
        # whole UTC second -> its ISO-8601 prefix ("2023-11-14T22:13:20.")
        self._iso_prefix: Dict[int, str] = {}
        # Deadline per type, armed on first insert into an empty buffer
        # (stream_insert_db.js:332-343); None = disarmed.
        self._deadlines: Dict[str, Optional[float]] = {t: None for t in self.column_sets}
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # trace plane: close sampled db_insert-queue trace contexts with a
        # "sink" span at buffer absorb (the last hop of the db_insert leg)
        from ..obs.attrib import STAGE_SINK_ABSORB, get_attrib
        from ..obs.trace import get_tracer

        self._obs_tracer = get_tracer()
        # wall-clock attribution (obs.attrib): each insert flush is a span
        # of the sink_absorb stage — the interval DBStats already times —
        # counting the rows inserted and those of them converted from tx
        # lines; shared, since the timer thread flushes too
        self._att_absorb = get_attrib().clock(
            STAGE_SINK_ABSORB, counters=("rows", "lines"), shared=True)
        if start_timer:
            self._thread = threading.Thread(target=self._timer_loop, daemon=True, name="dbwriter-timer")
            self._thread.start()

    # -- intake --------------------------------------------------------------
    def consume_line(self, line: str, headers: Optional[dict] = None) -> None:
        """CSV line off the db_insert queue (consumeMsg, stream_insert_db.js:355-376).

        A tx line is buffered as it is and becomes its insert tuple at
        flush (``_line_values``); every other line becomes its Entry here."""
        if line.startswith("tx|"):
            if headers:
                self._trace_absorb(headers, "tx")
            self.add("tx", line)
            return
        entry = self._factory.from_csv(line)
        if entry is None:
            if self.logger:
                self.logger.info(f"Entry undefined: {line}")
            return
        if headers:
            self._trace_absorb(headers, entry.type)
        self.add_entry(entry)

    def _trace_absorb(self, headers: dict, entry_type: str) -> None:
        tid = headers.get("trace_id")
        if tid is not None:
            # sampled message: mark the sink absorb under its trace_id
            now = time.time()
            start = headers.get("ingest_ts")
            self._obs_tracer.span(
                tid, "sink",
                now if start is None else float(start), now,
                entry_type=entry_type,
            )

    def add_entry(self, entry: Entry) -> None:
        if entry.type not in self.column_sets:
            if self.logger:
                self.logger.info(f"Not a tx, fs, al, or jx: {entry}")
            return
        try:
            obj = entry.to_postgres()
        except Exception as e:
            if self.logger:
                self.logger.error(f"to_postgres for type:{entry.type} threw an error: {e}")
            return
        self.add(entry.type, obj)

    def add(self, etype: str, obj: dict | str) -> None:
        with self._lock:
            flush_now = len(self._buffers[etype]) >= self.buffer_limit
        # Reference order: flush the full buffer first, then append
        # (stream_insert_db.js:345-352).
        if flush_now:
            self.process_buffer(etype)
        with self._lock:
            self._buffers[etype].append(obj)
            # arm whenever no deadline is pending — covers both the
            # first-insert-into-empty-buffer case and the row that lands
            # right after a limit-triggered flush disarmed the timer
            if self._deadlines[etype] is None:
                self._deadlines[etype] = self.clock() + self.max_ms / 1000.0
                self._wake.set()

    # -- flush ---------------------------------------------------------------
    def process_buffer(self, etype: str) -> bool:
        """Flush one type's buffer. Returns True when the insert succeeded
        (or the buffer was empty)."""
        with self._lock:
            drained = self._buffers[etype]
            if not drained:
                return True
            self._buffers[etype] = []
            self._deadlines[etype] = None
        cs = self.column_sets[etype]
        kept = drained
        start = time.perf_counter()
        try:
            with self._att_absorb.span():
                values, kept, n_lines = self._values(cs, drained)
                if values:
                    self.executor.insert_rows(cs, values)
        except Exception as e:
            if self.logger:
                self.logger.error(f"Error during insert attempt: {e}")
            with self._lock:
                # Drained rows go back in FRONT of anything newer
                # (stream_insert_db.js:310-320) and the timeout re-arms so
                # retry happens even if no new rows arrive.
                self._buffers[etype] = kept + self._buffers[etype]
                if self._deadlines[etype] is None:
                    self._deadlines[etype] = self.clock() + self.max_ms / 1000.0
                    self._wake.set()
            return False
        elapsed = time.perf_counter() - start
        if not values:
            return True
        self._att_absorb.count("rows", len(values))
        self._att_absorb.count("lines", n_lines)
        if self.db_stats is not None:
            self.db_stats.add_inserted(len(values))
            self.db_stats.add_elapsed_ms(elapsed * 1000.0)
        return True

    def _values(self, cs: ColumnSet, rows: list) -> Tuple[List[tuple], list, int]:
        """Insert tuples of buffered rows, in arrival order: each run of tx
        lines in one columnar pass, dict rows adapted column by column.
        Returns the tuples, the rows that gave them (a line the old
        Entry path rejected is logged and dropped) and how many came from
        lines."""
        values: List[tuple] = []
        kept: list = []
        n_lines = 0
        for is_line, run in groupby(rows, key=_is_line):
            run = list(run)
            if is_line:
                got, run = self._line_values(run)
                n_lines += len(got)
            else:
                got = adapt_rows(cs, run)
            values += got
            kept += run
        return values, kept, n_lines

    def _line_values(self, lines: List[str]) -> Tuple[List[tuple], List[str]]:
        """Insert tuples of tx lines, each equal to
        ``tuple(_adapt(v) for v in from_csv(line).to_postgres().values())``,
        with no Entry, dict or datetime per line. Returns them and the lines
        that gave them: when a line has too few fields or a timestamp out of
        datetime's range, each line is taken on its own, and a line that
        fails takes the Entry path, which logs and drops it."""
        try:
            return self._line_columns(lines), lines
        except _LINE_ERRORS:
            pass
        values, kept = [], []
        for line in lines:
            try:
                row = self._line_columns([line])[0]
            except _LINE_ERRORS:
                row = self._entry_values(line)
                if row is None:
                    continue
            values.append(row)
            kept.append(line)
        return values, kept

    def _line_columns(self, lines: List[str]) -> List[tuple]:
        ends, starts, servers, services, logids, accts, elapseds, tops = zip(
            *map(_TX_FIELDS, [line.split("|") for line in lines]))
        iso = self._iso_column
        return list(zip(
            iso(ends), iso(starts), servers, services, logids,
            map(_tx_int, accts), map(_tx_int, elapseds), tops,
        ))

    def _iso_column(self, fields: Sequence[str]) -> List[Optional[str]]:
        """endts/startts column values of ms fields: a column of plain
        digit runs in one pass, any other field by ``_ms_iso``."""
        if all(fields):
            digits = "".join(fields)
            if digits.isascii() and digits.isdigit():
                ms = list(map(int, fields))
                if max(ms) < _EXACT_MS:
                    get, second = self._iso_prefix.get, self._iso_second
                    return [(get(m // 1000) or second(m // 1000)) + _MS_SUFFIX[m % 1000]
                            for m in ms]
        return list(map(self._ms_iso, fields))

    def _ms_iso(self, field: str) -> Optional[str]:
        """endts/startts column value: the ISO-8601 Z string of
        ``datetime.fromtimestamp(js_parse_int(field) / 1000.0, tz=utc)``,
        None for NaN."""
        if field.isascii() and field.isdigit():
            ms = int(field)
        elif field == "NaN":
            return None
        else:
            ms = js_parse_int(field)
            if ms != ms:
                return None
        if not -_EXACT_MS < ms < _EXACT_MS:
            return _iso_z(datetime.fromtimestamp(float(ms) / 1000.0, tz=timezone.utc))
        sec, rem = divmod(int(ms), 1000)
        return (self._iso_prefix.get(sec) or self._iso_second(sec)) + _MS_SUFFIX[rem]

    def _iso_second(self, sec: int) -> str:
        """The ISO-8601 prefix of a whole UTC second, cached."""
        if len(self._iso_prefix) >= _ISO_PREFIX_CACHE:
            self._iso_prefix.clear()
        # "...T22:13:20.000Z" less "000Z"
        prefix = self._iso_prefix[sec] = _iso_z(datetime.fromtimestamp(sec, tz=timezone.utc))[:-4]
        return prefix

    def _entry_values(self, line: str) -> Optional[tuple]:
        """The Entry path for one tx line: its insert tuple, or None after
        logging why it is dropped."""
        try:
            entry = self._factory.from_csv(line)
        except OverflowError:  # a numeric field past float's range
            entry = None
        if entry is None:
            if self.logger:
                self.logger.info(f"Entry undefined: {line}")
            return None
        try:
            obj = entry.to_postgres()
        except Exception as e:
            if self.logger:
                self.logger.error(f"to_postgres for type:{entry.type} threw an error: {e}")
            return None
        return adapt_rows(self.column_sets[entry.type], [obj])[0]

    def process_all(self) -> None:
        """Flush everything (processAllBuffers, on shutdown)."""
        for etype in self.column_sets:
            self.process_buffer(etype)

    def process_due(self, now: Optional[float] = None) -> List[str]:
        """Flush every buffer whose deadline has passed; returns flushed types."""
        now = self.clock() if now is None else now
        due = []
        with self._lock:
            for etype, deadline in self._deadlines.items():
                if deadline is not None and now >= deadline:
                    due.append(etype)
        for etype in due:
            self.process_buffer(etype)
        return due

    def _timer_loop(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                deadlines = [d for d in self._deadlines.values() if d is not None]
            if not deadlines:
                self._wake.wait(timeout=1.0)
                self._wake.clear()
                continue
            wait = min(deadlines) - self.clock()
            if wait > 0:
                self._wake.wait(timeout=wait)
                self._wake.clear()
                continue
            self.process_due()

    # -- resume (§5.4) -------------------------------------------------------
    def buffered_counts(self) -> Dict[str, int]:
        with self._lock:
            return {t: len(b) for t, b in self._buffers.items()}

    def save_resume(self, path: str) -> None:
        with self._lock:
            buffers = {t: list(b) for t, b in self._buffers.items()}
        payload = {t: self._resume_rows(t, b) for t, b in buffers.items()}
        save_resume_file(path, payload, logger=self.logger)

    def _resume_rows(self, etype: str, rows: list) -> List[dict]:
        """The resume file's rows: a tx line as the dict of its column
        values (the JSON its Entry's ``to_postgres`` dict gives), a dict
        row through ``_resume_row``."""
        cols = self.column_sets[etype].columns
        out: List[dict] = []
        for is_line, run in groupby(rows, key=_is_line):
            if is_line:
                out += [dict(zip(cols, v)) for v in self._line_values(list(run))[0]]
            else:
                out += [self._resume_row(r) for r in run]
        return out

    @classmethod
    def _resume_row(cls, value):
        """Recursive datetime -> ISO adaptation ('al' rows nest an entry dict
        that itself contains datetimes)."""
        if isinstance(value, datetime):
            return _adapt(value)
        if isinstance(value, dict):
            return {k: cls._resume_row(v) for k, v in value.items()}
        if isinstance(value, list):
            return [cls._resume_row(v) for v in value]
        return value

    def load_resume(self, path: str) -> bool:
        data = load_resume_file(path, logger=self.logger)
        if not isinstance(data, dict):
            return False
        with self._lock:
            for etype in self.column_sets:
                rows = data.get(etype)
                if isinstance(rows, list) and rows:
                    self._buffers[etype] = list(rows) + self._buffers[etype]
                    if self._deadlines[etype] is None:
                        self._deadlines[etype] = self.clock() + self.max_ms / 1000.0
            self._wake.set()
        return True

    # -- lifecycle -----------------------------------------------------------
    def close(self, *, flush: bool = True) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        if flush:
            self.process_all()
        self.executor.close()
