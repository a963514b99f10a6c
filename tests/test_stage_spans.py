"""Stage spans and counters on the attribution plane: ``StageClock.span``
accumulates as ``add_busy`` does and lands on the profiler's host plane;
counters ride the clock into ``snapshot()``, ``/attrib`` and the registry;
the spans and counters the program keeps at its stage boundaries
(``parser_sweep``, ``ingest_dispatch``, ``state_save``, ``gc``)."""

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
    STAGE_STATE_SAVE,
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


def _driver(micro_batch_size=512):
    from apmbackend_tpu.pipeline import PipelineDriver

    cfg = default_config()
    cfg["tpuEngine"]["serviceCapacity"] = 16
    return PipelineDriver(cfg, micro_batch_size=micro_batch_size)


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
