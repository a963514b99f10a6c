"""Stage spans and counters on the attribution plane: ``StageClock.span``
accumulates as ``add_busy`` does and lands on the profiler's host plane;
counters ride the clock into ``snapshot()``, ``/attrib`` and the registry;
the spans and counters the program keeps at its stage boundaries
(``parser_sweep``, ``ingest_dispatch``, ``state_save``, ``state_restore``,
``tick_emit``, ``sink_absorb``, ``gc``)."""

import gc
import glob
import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from apmbackend_tpu.config import default_config
from apmbackend_tpu.entries import TxEntry
from apmbackend_tpu.obs import MetricsRegistry, TelemetryServer, parse_prom_text, set_registry
from apmbackend_tpu.obs import attrib
from apmbackend_tpu.obs.attrib import (
    NULL_CLOCK,
    SPAN_STAGES,
    STAGE_GC,
    STAGE_INGEST_DISPATCH,
    STAGE_PARSER_SWEEP,
    STAGE_SINK_ABSORB,
    STAGE_STATE_RESTORE,
    STAGE_STATE_SAVE,
    STAGE_TICK_EMIT,
    AttributionPlane,
    StageClock,
    get_attrib,
    merge_snapshots,
    set_attrib,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_plane():
    old_plane = set_attrib(AttributionPlane(module="w"))
    old_reg = set_registry(MetricsRegistry())
    yield get_attrib()
    set_attrib(old_plane)
    set_registry(old_reg)


class _Ticks:
    """A perf_counter that advances by the given steps."""

    def __init__(self, *values):
        self.values = list(values)

    def __call__(self):
        return self.values.pop(0)


@pytest.mark.parametrize("t0,t1", [(10.0, 10.25), (5.0, 5.0)])
def test_span_accumulates_exactly_as_add_busy(monkeypatch, t0, t1):
    spanned, added = StageClock("a"), StageClock("b")
    monkeypatch.setattr(attrib.time, "perf_counter", _Ticks(t0, t1))
    with spanned.span():
        pass
    added.add_busy(t1 - t0)
    assert spanned.snapshot() == added.snapshot()
    assert spanned.events == (1 if t1 > t0 else 0)


def test_span_counts_the_block_even_when_it_raises():
    clock = StageClock("a")
    with pytest.raises(ValueError):
        with clock.span():
            raise ValueError("bad batch")
    assert clock.events == 1 and clock.busy_s > 0.0


def test_disabled_plane_hands_out_noop_clock_span_and_counter(monkeypatch):
    monkeypatch.setenv("APM_NO_ATTRIB", "1")
    plane = AttributionPlane()
    clock = plane.clock(STAGE_INGEST_DISPATCH, counters=("records",))
    assert clock is NULL_CLOCK
    with clock.span() as sp:
        assert sp is not None
    clock.count("records", 5)
    assert clock.snapshot() == {"busy_s": 0.0, "blocked_s": 0.0, "idle_s": 0.0, "events": 0}
    plane.track_gc()
    gc.collect()
    assert plane.snapshot()["stages"] == {}


def test_counters_in_snapshot_attrib_and_collect(fresh_plane):
    clock = fresh_plane.clock(STAGE_INGEST_DISPATCH, counters=("records", "slots"))
    assert clock.snapshot()["records"] == 0  # declared before the first count
    with clock.span():
        clock.count("records", 3)
        clock.count("slots", 256)
    snap = fresh_plane.snapshot()["stages"][STAGE_INGEST_DISPATCH]
    assert (snap["records"], snap["slots"], snap["events"]) == (3, 256, 1)

    reg = MetricsRegistry()
    fresh_plane.install(reg)
    got = {(lb["stage"], lb["counter"]): v for name, lb, v in parse_prom_text(reg.render())
           if name == "apm_stage_counter_total"}
    assert got == {(STAGE_INGEST_DISPATCH, "records"): 3, (STAGE_INGEST_DISPATCH, "slots"): 256}

    server = TelemetryServer(MetricsRegistry(), port=0, module="w")
    server.start()
    try:
        with urllib.request.urlopen(f"{server.url}/attrib", timeout=5) as resp:
            body = json.loads(resp.read())
    finally:
        server.stop()
    assert body["stages"][STAGE_INGEST_DISPATCH]["slots"] == 256

    merged = merge_snapshots([fresh_plane.snapshot(), fresh_plane.snapshot()])
    assert merged["stages"][STAGE_INGEST_DISPATCH]["records"] == 6
    assert "busy_share" not in merged["stages"][STAGE_INGEST_DISPATCH]


def test_span_lands_on_the_profiler_host_plane(tmp_path, fresh_plane):
    import jax
    from jax.profiler import ProfileData

    clock = fresh_plane.clock(STAGE_PARSER_SWEEP)
    with clock.span():  # no trace recording: only the clock
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with clock.span():
            sum(range(10_000))
    finally:
        jax.profiler.stop_trace()
    assert clock.events == 2
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:CPU")
              for line in plane.lines for ev in line.events
              if ev.name == STAGE_PARSER_SWEEP]
    assert len(events) == 1
    assert 0 < events[0].duration_ns <= clock.busy_s * 1e9 + 1e6


def test_parser_sweep_counts_released_records_and_failed_sweeps(fresh_plane):
    from apmbackend_tpu.ingest.parser import TransactionParser
    from apmbackend_tpu.ingest.replay import FixtureGenerator

    now = [0.0]
    records = []
    parser = TransactionParser(lambda tx, db: records.append(tx),
                               server_from_path=lambda fp: "jvm1", clock=lambda: now[0])
    gen = FixtureGenerator(server="jvm1")
    for fname, line in gen.soap_transaction("getBar", 250):  # no account: parked
        parser.read_line(fname, line)
    clock = fresh_plane.stage_table()[STAGE_PARSER_SWEEP]
    assert (clock["released"], clock["failed"]) == (0, 0)
    parser.sweep()
    assert records == []
    now[0] = 31  # past the 30 s need-number TTL
    parser.sweep()
    assert len(records) == 1
    st = fresh_plane.stage_table()[STAGE_PARSER_SWEEP]
    assert (st["released"], st["failed"], st["events"]) == (1, 0, 2)

    def broken():
        raise RuntimeError("dictionary changed size during iteration")

    parser.acct_cache.sweep = broken
    with pytest.raises(RuntimeError):
        parser.sweep()
    st = fresh_plane.stage_table()[STAGE_PARSER_SWEEP]
    assert (st["released"], st["failed"], st["events"]) == (1, 1, 3)


@pytest.mark.parametrize("native", [True, False], ids=["native", "python"])
def test_parser_sweep_examines_due_entries_not_live_ones(fresh_plane, native):
    from apmbackend_tpu.ingest.parser import TransactionParser
    from apmbackend_tpu.ingest.replay import FixtureGenerator

    now = [0.0]
    records = []
    parser = TransactionParser(lambda tx, db: records.append(tx), use_native=native,
                               server_from_path=lambda fp: "jvm1", clock=lambda: now[0])
    for i in range(100_000):  # live for the 120 s account TTL
        parser.acct_cache.set(f"acct{i}", "123456789")
    gen = FixtureGenerator(server="jvm1")
    for i in range(5):  # no account: parked for the 30 s need-number TTL
        for fname, line in gen.soap_transaction(f"svc{i}", 100 + i):
            parser.read_line(fname, line)
    assert len(parser.need_num_cache) == 5 and records == []
    now[0] = 31
    parser.sweep()
    assert sorted(tx.service for tx in records) == [f"S:svc{i}" for i in range(5)]
    st = fresh_plane.stage_table()[STAGE_PARSER_SWEEP]
    assert (st["released"], st["failed"]) == (5, 0)
    assert st["examined"] <= st["released"] + 3
    assert len(parser.acct_cache) == 100_000


def _driver(micro_batch_size=512, lags=None, **kw):
    from apmbackend_tpu.pipeline import PipelineDriver

    cfg = default_config()
    cfg["tpuEngine"]["serviceCapacity"] = 16
    if lags is not None:
        cfg["streamCalcZScore"]["defaults"] = lags
    return PipelineDriver(cfg, micro_batch_size=micro_batch_size, **kw)


def _txs(n, label=170_000_000):
    return [TxEntry("jvm1", f"S:s{i % 3}", f"l{i}", "1", label * 10000 + i - 100,
                    label * 10000 + i, 100 + i, "Y") for i in range(n)]


@pytest.mark.parametrize("path", ["feed", "csv"])
def test_ingest_dispatch_counts_records_and_both_pad_tiers(fresh_plane, path):
    drv = _driver(micro_batch_size=512)
    small, large = _txs(10), _txs(300)
    for batch in (small, large):
        if path == "feed":
            for tx in batch:
                drv.feed(tx)
            drv.flush()
        else:
            drv.feed_csv_batch([tx.to_csv() for tx in batch])
    st = fresh_plane.stage_table()[STAGE_INGEST_DISPATCH]
    assert st["events"] == 2
    assert st["records"] == 310
    assert st["slots"] == 256 + 512  # the small tier, then the micro-batch tier


def test_state_save_spans_engine_and_resume_file_writes(tmp_path, fresh_plane):
    from apmbackend_tpu.utils.resume import save_resume_file

    drv = _driver()
    for tx in _txs(20):
        drv.feed(tx)
    drv.flush()
    path = str(tmp_path / "engine.resume.npz")
    drv.save_resume(path)
    st = fresh_plane.stage_table()[STAGE_STATE_SAVE]
    assert st["events"] == 1 and st["busy_s"] > 0.0
    assert st["bytes"] == os.path.getsize(path)

    alerts = str(tmp_path / "alerts.resume")
    save_resume_file(alerts, {"alerts": {"a": 1}, "alertBuffer": []})
    st = fresh_plane.stage_table()[STAGE_STATE_SAVE]
    assert st["events"] == 2
    assert st["bytes"] == os.path.getsize(path) + os.path.getsize(alerts)


def _tick_through(drv, intervals, spike_at=None, label0=170_000_000):
    """Six transactions on each of three keys per 10 s interval, their
    elapsed times moving from one interval to the next; x20 at ``spike_at``."""
    for k in range(intervals):
        label = label0 + k
        for i in range(18):
            elapsed = (100 + 7 * i + 37 * (k % 5)) * (20 if k == spike_at else 1)
            drv.feed(TxEntry("jvm1", f"S:s{i % 3}", f"l{k}-{i}", "1",
                             label * 10000 + i - elapsed, label * 10000 + i, elapsed, "Y"))
        drv.flush()


@pytest.mark.parametrize("source", ["npz", "chain"])
def test_state_restore_spans_the_driver_restore_and_resume_file_reads(
        tmp_path, fresh_plane, source):
    from apmbackend_tpu.deltachain import DeltaChain
    from apmbackend_tpu.utils.resume import load_resume_file, save_resume_file

    drv = _driver(lags=[{"LAG": 4, "THRESHOLD": 3.0, "INFLUENCE": 0.0},
                        {"LAG": 6, "THRESHOLD": 3.0, "INFLUENCE": 0.0}])
    _tick_through(drv, 12)
    if source == "npz":
        path = str(tmp_path / "engine.resume.npz")
        drv.save_resume(path)
        with np.load(path, allow_pickle=True) as npz:
            data = {name: npz[name] for name in npz.files}
        read = os.path.getsize(path)
    else:
        chain = DeltaChain(str(tmp_path / "chain"), fsync=False)
        data = drv._capture_resume_arrays(None)
        chain.initialize(data, epoch=0)
        read = sum(os.path.getsize(p) for p in glob.glob(str(tmp_path / "chain" / "base-*")))
    assert fresh_plane.stage_table()[STAGE_STATE_RESTORE]["events"] == 0  # saving reads nothing

    fresh = _driver(lags=[{"LAG": 4, "THRESHOLD": 3.0, "INFLUENCE": 0.0},
                          {"LAG": 6, "THRESHOLD": 3.0, "INFLUENCE": 0.0}])
    assert STAGE_STATE_RESTORE in SPAN_STAGES
    if source == "npz":
        assert fresh.load_resume(path)
    else:
        assert fresh.load_resume_chain(str(tmp_path / "chain"))
    st = fresh_plane.stage_table()[STAGE_STATE_RESTORE]
    valid = sum(int(np.count_nonzero(~np.isnan(data[f"z{lag}_values"]))) for lag in (4, 6))
    assert valid > 0
    assert st["events"] == 1 and st["busy_s"] > 0.0
    assert (st["bytes"], st["keys"], st["valid_slots"]) == (read, 3, valid)
    assert all(st[c] > 0.0 for c in ("load_ms", "install_ms", "derive_ms"))
    assert st["load_ms"] + st["install_ms"] + st["derive_ms"] <= 1e3 * st["busy_s"]

    alerts = str(tmp_path / "alerts.resume")
    save_resume_file(alerts, {"alerts": {"a": 1}, "alertBuffer": []})
    assert load_resume_file(alerts) == {"alerts": {"a": 1}, "alertBuffer": []}
    assert load_resume_file(str(tmp_path / "absent.resume")) is None  # no restore, no span
    st = fresh_plane.stage_table()[STAGE_STATE_RESTORE]
    assert st["events"] == 2 and st["bytes"] == read + os.path.getsize(alerts)


def test_partition_handoff_is_no_restore(fresh_plane, monkeypatch):
    """A handoff re-installs rows through the resume path but reads no
    state: it leaves ``state_restore`` as it was and waits on no device."""
    import jax

    drv = _driver(lags=[{"LAG": 4, "THRESHOLD": 3.0, "INFLUENCE": 0.0}])
    _tick_through(drv, 6)
    waits = []
    monkeypatch.setattr(jax, "block_until_ready", lambda x: waits.append(x) or x)
    data = drv.export_service_rows(lambda srv, svc: svc == "S:s1")
    assert drv.remove_service_rows(lambda srv, svc: svc == "S:s1") == 1
    assert drv.import_service_rows(data) == 1
    assert waits == []
    st = fresh_plane.stage_table().get(STAGE_STATE_RESTORE, {})
    assert st.get("events", 0) == 0
    assert all(st.get(c, 0) == 0 for c in ("keys", "valid_slots", "install_ms", "derive_ms"))


def test_tick_emit_counts_banded_and_signalled_rows(fresh_plane):
    rows = []
    drv = _driver(lags=[{"LAG": 3, "THRESHOLD": 2.0, "INFLUENCE": 0.0}], on_fullstat=rows.append)
    _tick_through(drv, 24, spike_at=14)
    drv.drain_emission()
    st = fresh_plane.stage_table()[STAGE_TICK_EMIT]
    parts = ("average", "per75", "per95")

    def held(v):
        return v is not None and v == v

    banded = sum(any(held(getattr(r, p + "_lb")) for p in parts) for r in rows)
    signals = sum(any(getattr(r, p + "_signal") for p in parts) for r in rows)
    assert banded > 0 and signals > 0
    assert (st["banded"], st["signals"]) == (banded, signals)


def test_sink_absorb_counts_rows_and_rows_from_tx_lines(fresh_plane):
    from apmbackend_tpu.entries import FullStatEntry
    from apmbackend_tpu.sinks import DBWriter, FakeExecutor

    ex = FakeExecutor()
    w = DBWriter(ex, {"dbInsertBufferLimit": 100}, start_timer=False)
    fs = FullStatEntry(1700000000000, "s", "svc", 2.5, 360, *range(15))
    for i in range(5):
        w.consume_line(TxEntry("s", "svc", f"log{i}", 42, 1, 2, 1, "Y").to_csv())
    w.consume_line("tx|s|svc|short")  # dropped at flush: no row
    w.add_entry(TxEntry("s", "svc", "dict", 42, 1, 2, 1, "Y"))  # a dict row
    w.consume_line(fs.to_csv())
    w.consume_line(fs.to_csv())
    st = fresh_plane.stage_table()[STAGE_SINK_ABSORB]
    assert (st["rows"], st["lines"], st["events"]) == (0, 0, 0)  # declared
    w.process_all()
    st = fresh_plane.stage_table()[STAGE_SINK_ABSORB]
    assert ex.batches == [("tx", 6), ("stats", 2)]
    assert (st["rows"], st["lines"], st["events"]) == (8, 5, 2)
    ex.fail = True
    w.consume_line(TxEntry("s", "svc", "log5", 42, 1, 2, 1, "Y").to_csv())
    w.process_all()  # a failed insert counts no row
    st = fresh_plane.stage_table()[STAGE_SINK_ABSORB]
    assert (st["rows"], st["lines"], st["events"]) == (8, 5, 3)


def test_shared_clock_loses_no_update_across_threads(fresh_plane):
    import threading

    clock = fresh_plane.clock(STAGE_STATE_SAVE, counters=("bytes",), shared=True)
    assert fresh_plane.clock(STAGE_STATE_SAVE) is clock

    def save():
        for _ in range(2000):
            with clock.span():
                clock.count("bytes", 3)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=save) for _ in range(2 * (os.cpu_count() or 4))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert clock.counts["bytes"] == 3 * 2000 * len(threads)


def test_gc_clock_times_collections_and_survives_reset(fresh_plane):
    fresh_plane.track_gc()
    fresh_plane.track_gc()  # once per process: one hook
    assert gc.callbacks.count(attrib._on_gc) == 1
    gc.collect()
    st = fresh_plane.stage_table()[STAGE_GC]
    assert st["events"] >= 1 and st["busy_s"] > 0.0
    fresh_plane.reset()
    assert fresh_plane.stage_table()[STAGE_GC]["events"] == 0
    gc.collect()
    assert fresh_plane.stage_table()[STAGE_GC]["events"] >= 1
    assert set(SPAN_STAGES) >= {STAGE_GC, STAGE_STATE_SAVE, STAGE_INGEST_DISPATCH,
                                STAGE_PARSER_SWEEP}


def test_obs_import_and_spans_leave_jax_unimported():
    code = (
        "import sys\n"
        "import apmbackend_tpu.obs\n"
        "from apmbackend_tpu.obs.attrib import get_attrib\n"
        "c = get_attrib().clock('parser_scan')\n"
        "with c.span():\n"
        "    pass\n"
        "assert c.events == 1, c.snapshot()\n"
        "assert 'jax' not in sys.modules, 'obs imported jax'\n"
    )
    env = dict(os.environ, APM_NO_ATTRIB="0")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_spans_leave_the_driver_state_unchanged(fresh_plane):
    """The spans wrap, never alter, what the driver dispatches."""
    plain = _driver()
    set_attrib(AttributionPlane(enabled=False))
    off = _driver()
    for drv in (plain, off):
        for tx in _txs(40):
            drv.feed(tx)
        drv.flush()
    assert np.array_equal(np.asarray(plain.state.stats.counts),
                          np.asarray(off.state.stats.counts))
