"""DB sink: buffering, flush triggers, failure requeue, resume (stream_insert_db.js role)."""

import math

import pytest

from apmbackend_tpu.entries import (
    AlertEntry,
    EntryFactory,
    FullStatEntry,
    JmxEntry,
    StatEntry,
    TxEntry,
)
from apmbackend_tpu.sinks import (
    DBWriter,
    FakeExecutor,
    SQLiteExecutor,
    column_sets_from_config,
)
from apmbackend_tpu.sinks.db import _adapt
from apmbackend_tpu.utils.counters import DBStats


def make_writer(limit=3, max_ms=5000, executor=None, **kw):
    executor = executor or FakeExecutor()
    cfg = {"dbInsertBufferLimit": limit, "dbMaxTimeBetweenInsertsMs": max_ms}
    clock = FakeClock()
    w = DBWriter(executor, cfg, clock=clock, start_timer=False, **kw)
    return w, executor, clock


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def tx(i=0):
    return TxEntry("srv1", "svc", f"log{i}", 42, 1700000000000 + i, 1700000005000 + i, 5000, "Y")


def test_column_sets_table_names():
    cs = column_sets_from_config({"dbTxTable": "mytx", "dbStatTable": "st8"})
    assert cs["tx"].table == "mytx"
    assert cs["fs"].table == "st8"
    assert cs["al"].table == "alerts"
    assert "acctnum" in cs["tx"].columns
    assert len(cs["jx"].columns) == 18


def test_flush_at_buffer_limit_reference_order():
    # The flush fires when a new row finds the buffer already AT the limit:
    # the full batch is inserted first, then the new row starts a fresh buffer
    # (stream_insert_db.js:345-352).
    w, ex, _ = make_writer(limit=3)
    for i in range(3):
        w.add_entry(tx(i))
    assert ex.batches == []  # at limit but not over: no flush yet
    w.add_entry(tx(3))
    assert ex.batches == [("tx", 3)]
    assert w.buffered_counts()["tx"] == 1


def test_timeout_flush_via_deadline():
    w, ex, clock = make_writer(limit=100, max_ms=5000)
    w.add_entry(tx())
    assert w.process_due() == []  # not due yet
    clock.t += 5.1
    assert w.process_due() == ["tx"]
    assert ex.batches == [("tx", 1)]
    # deadline disarmed after flush
    clock.t += 10
    assert w.process_due() == []


def test_failure_requeues_in_front_and_rearms():
    w, ex, clock = make_writer(limit=2)
    w.add_entry(tx(1))
    w.add_entry(tx(2))
    ex.fail = True
    w.add_entry(tx(3))  # triggers flush of [1,2], which fails
    assert w.buffered_counts()["tx"] == 3
    ex.fail = False
    clock.t += 6
    w.process_due()
    assert ex.batches == [("tx", 3)]
    # order preserved: 1, 2, 3
    logids = [row[4] for row in ex.tables["tx"]]
    assert logids == ["log1", "log2", "log3"]


def test_consume_line_types():
    w, ex, _ = make_writer(limit=100)
    w.consume_line(tx().to_csv())
    st = StatEntry(1700000000000, "s", "svc", 2.5, 100.0, 120.0, 200.0)
    w.consume_line(st.to_csv())  # plain stats are rejected (consumeMsg :364-376)
    w.consume_line("garbage line")
    fs = FullStatEntry(
        1700000000000, "s", "svc", 2.5, 360,
        100.0, 90.0, 80.0, 110.0, 0,
        120.0, 100.0, 90.0, 130.0, 0,
        200.0, 150.0, 100.0, 220.0, 1,
    )
    w.consume_line(fs.to_csv())
    al = AlertEntry(1700000001000, 1700000000000, "s", "svc", "cause", fs.to_csv())
    w.consume_line(al.to_csv())
    jx = JmxEntry(1700000000000, "host1", *range(16))
    w.consume_line(jx.to_csv())
    counts = w.buffered_counts()
    assert counts == {"tx": 1, "fs": 1, "al": 1, "jx": 1}


def test_resume_roundtrip(tmp_path):
    path = str(tmp_path / "db_buffer.resume")
    w, ex, _ = make_writer(limit=100)
    w.add_entry(tx(7))
    w.add_entry(JmxEntry(1700000000000, "host1", *range(16)))
    w.save_resume(path)

    w2, ex2, clock2 = make_writer(limit=100)
    assert w2.load_resume(path)
    counts = w2.buffered_counts()
    assert counts["tx"] == 1 and counts["jx"] == 1
    clock2.t += 6
    w2.process_due()
    assert ("tx", 1) in ex2.batches and ("jmx", 1) in ex2.batches
    # datetimes survived as ISO-8601 Z strings (JS Date.toJSON shape)
    endts = ex2.tables["tx"][0][0]
    assert isinstance(endts, str) and endts.endswith("Z")


def test_deadline_rearms_after_limit_flush():
    # The row appended right after a limit-triggered flush must still get a
    # timeout flush (trickling traffic after a burst).
    w, ex, clock = make_writer(limit=2, max_ms=5000)
    for i in range(3):
        w.add_entry(tx(i))  # third add flushes [0,1], buffers [2]
    assert ex.batches == [("tx", 2)]
    clock.t += 5.1
    assert w.process_due() == ["tx"]
    assert ex.batches == [("tx", 2), ("tx", 1)]


def test_alert_row_resume_roundtrip(tmp_path):
    # 'al' rows nest an entry dict with datetimes: resume must serialize them
    fs = FullStatEntry(
        1700000000000, "s", "svc", 2.5, 360,
        100.0, 90.0, 80.0, 110.0, 0,
        120.0, 100.0, 90.0, 130.0, 0,
        200.0, 150.0, 100.0, 220.0, 1,
    )
    al = AlertEntry(1700000001000, 1700000000000, "s", "svc", "cause", fs.to_csv())
    path = str(tmp_path / "al.resume")
    w, _, _ = make_writer(limit=100)
    w.add_entry(al)
    w.save_resume(path)
    w2, ex2, _ = make_writer(limit=100)
    assert w2.load_resume(path)
    w2.process_all()
    assert ("alerts", 1) in ex2.batches


def test_load_resume_missing(tmp_path):
    w, _, _ = make_writer()
    assert not w.load_resume(str(tmp_path / "nope.resume"))


def test_sqlite_executor_end_to_end():
    ex = SQLiteExecutor(":memory:")
    stats = DBStats()
    w, _, _ = make_writer(limit=2, executor=ex, db_stats=stats)
    for i in range(5):
        w.add_entry(tx(i))
    w.process_all()
    rows = ex._conn.execute("SELECT COUNT(*), MIN(acctnum) FROM tx").fetchone()
    assert rows == (5, 42)
    assert stats.rec_ins_counter == 5
    snap = stats.snapshot_and_reset()
    assert "inserted: 5" in snap
    w.close()


def test_nan_becomes_null_in_sqlite():
    ex = SQLiteExecutor(":memory:")
    w, _, _ = make_writer(limit=100, executor=ex)
    t = tx()
    t.acct_num = math.nan
    t.elapsed = math.nan
    w.add_entry(t)
    w.process_all()
    row = ex._conn.execute("SELECT acctnum, elapsed FROM tx").fetchone()
    assert row == (None, None)
    w.close()


def test_background_timer_thread_flushes():
    ex = FakeExecutor()
    w = DBWriter(ex, {"dbInsertBufferLimit": 100, "dbMaxTimeBetweenInsertsMs": 50}, start_timer=True)
    w.add_entry(tx())
    import time

    deadline = time.monotonic() + 2.0
    while not ex.batches and time.monotonic() < deadline:
        time.sleep(0.02)
    assert ex.batches == [("tx", 1)]
    w.close()


# -- tx lines: wire line -> insert tuple at flush ------------------------------

class ListLogger:
    def __init__(self):
        self.lines = []

    def info(self, msg):
        self.lines.append(msg)

    error = info


def entry_path(line):
    """What the Entry path makes of a tx line: its insert tuple and no log
    line, or None and the line it logs."""
    cols = column_sets_from_config({})["tx"].columns
    entry = EntryFactory().from_csv(line)
    if entry is None:
        return None, f"Entry undefined: {line}"
    try:
        obj = entry.to_postgres()
    except Exception as e:
        return None, f"to_postgres for type:tx threw an error: {e}"
    assert tuple(obj) == cols
    return tuple(_adapt(v) for v in obj.values()), None


def tx_line(acct="42", start="1700000000000", end="1700000005123", elapsed="5123", extra=""):
    return f"tx|srv1|svc|log1|{acct}|{start}|{end}|{elapsed}|Y{extra}"


TX_LINES = {
    "plain": tx_line(),
    "nan_acctnum": tx_line(acct="NaN"),
    "nan_elapsed": tx_line(elapsed="NaN"),
    "nan_start_end": tx_line(start="NaN", end="NaN"),
    "non_integer_numerics": tx_line(acct="12.7", start=" 1700000000000", end="1700000005123abc",
                                    elapsed=" 42"),
    "trailing_garbage": tx_line(acct="7abc", elapsed="+7"),
    "negative_ms": tx_line(start="-1", end="-86400001", elapsed="-5"),
    "pre_epoch_ms": tx_line(start="-2208988800000", end="-1"),
    "ms_000_and_999": tx_line(start="1700000000000", end="1700000000999"),
    "past_exact_window": tx_line(start="9999999999999", end="-9999999999999"),
    "past_datetime_range": tx_line(end="999999999999999999"),
    "too_few_fields": "tx|srv1|svc|log1|42|1700000000000|1700000005123",
    "extra_trailing_fields": tx_line(extra="|more|fields"),
    "unicode_digits": tx_line(acct="٤٢", end="١700000005123"),
    "past_float53": tx_line(acct="12345678901234567", elapsed="9007199254740993"),
}


@pytest.mark.parametrize("line", TX_LINES.values(), ids=TX_LINES.keys())
def test_tx_line_inserts_the_entry_paths_row(line):
    row, logged = entry_path(line)
    fake_log, sqlite_log = ListLogger(), ListLogger()
    fw, fake, _ = make_writer(limit=100, logger=fake_log)
    sw, lite, _ = make_writer(limit=100, executor=SQLiteExecutor(":memory:"), logger=sqlite_log)
    for w in (fw, sw):
        w.consume_line(line)
        assert w.buffered_counts()["tx"] == 1
        w.process_all()
    if row is None:
        assert fake.tables.get("tx", []) == []
        assert fake_log.lines == sqlite_log.lines == [logged]
        return
    assert fake.tables["tx"] == [row]
    assert lite._conn.execute("SELECT * FROM tx").fetchall() == [row]
    assert fake_log.lines == sqlite_log.lines == []
    sw.close()


def test_mixed_buffer_flushes_in_arrival_order(tmp_path):
    path = str(tmp_path / "db_buffer.resume")
    w0, _, _ = make_writer(limit=100)
    w0.add_entry(tx(1))
    w0.save_resume(path)
    w, ex, _ = make_writer(limit=100)
    w.consume_line(tx(0).to_csv())
    assert w.load_resume(path)  # restored rows go in front
    w.consume_line(tx(2).to_csv())
    w.add_entry(tx(3))
    w.consume_line(tx(4).to_csv())
    w.consume_line(tx(5).to_csv())
    w.process_all()
    assert [row[4] for row in ex.tables["tx"]] == [f"log{i}" for i in (1, 0, 2, 3, 4, 5)]
    assert ex.tables["tx"] == [entry_path(tx(i).to_csv())[0] for i in (1, 0, 2, 3, 4, 5)]
    assert ex.batches == [("tx", 6)]


def test_failed_insert_requeues_lines_in_front():
    log = ListLogger()
    w, ex, clock = make_writer(limit=100, logger=log)
    w.consume_line(tx(1).to_csv())
    w.consume_line(TX_LINES["too_few_fields"])
    w.consume_line(tx(2).to_csv())

    def fail_as_a_line_arrives(cs, values):
        w.consume_line(tx(3).to_csv())
        raise RuntimeError("injected insert failure")

    ex.insert_rows = fail_as_a_line_arrives
    assert not w.process_buffer("tx")
    assert w.buffered_counts()["tx"] == 3  # the short line is dropped, once
    assert log.lines[0] == f"Entry undefined: {TX_LINES['too_few_fields']}"
    del ex.insert_rows
    clock.t += 6
    assert w.process_due() == ["tx"]
    assert ex.batches == [("tx", 3)]
    assert [row[4] for row in ex.tables["tx"]] == ["log1", "log2", "log3"]
    assert len(log.lines) == 2  # the drop, then the failed insert


def test_save_resume_of_lines_writes_the_entry_paths_json(tmp_path):
    lines = [tx(i).to_csv() for i in range(3)] + [
        line for line in TX_LINES.values() if entry_path(line)[0] is not None]
    by_entry, _, _ = make_writer(limit=100)
    by_line, _, _ = make_writer(limit=100)
    for line in lines:
        by_entry.add_entry(EntryFactory().from_csv(line))  # dict rows
        by_line.consume_line(line)
    jx = JmxEntry(1700000000000, "host1", *range(16))
    for w in (by_entry, by_line):
        w.add_entry(jx)
    by_entry.save_resume(str(tmp_path / "entry.resume"))
    by_line.save_resume(str(tmp_path / "line.resume"))
    expected = (tmp_path / "entry.resume").read_bytes()
    assert (tmp_path / "line.resume").read_bytes() == expected
    assert by_line.buffered_counts()["tx"] == len(lines)  # a save keeps the buffer
