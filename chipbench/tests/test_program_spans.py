"""The readers of the program's stage spans and counters: each on a hand-built
run context, and the span reduction on a trace recorded on a v5e
(record_program_trace.py: program spans on two threads, harness spans
``feed`` and ``settle``, programs ``engine_ingest`` and ``engine_core_tick``)."""

import os

import pytest

from chipbench.harness import program_spans as ps
from chipbench.harness.spec import Cell, load_benchmark
from chipbench.harness.trace import TraceReading

MS = 1e6  # ns
NEW = ("sweep_busy_share.backfill", "save_busy_share.backfill",
       "ingest_dispatch_busy_share.backfill", "ingest_fill_share.backfill",
       "ingest_device_ms.backfill", "idle_unattributed_share.backfill")


def reading():
    """A 1 s window on one chip, busy 0-100 ms and 500-650 ms."""
    return TraceReading((0.0, 1000 * MS), {"/device:TPU:0": [(0.0, 100 * MS), (500 * MS, 650 * MS)]},
                        {"jit_engine_ingest": 0.15, "jit_engine_core_tick": 0.05}, [])


SPANS = [
    ("worker_feed", "t1", 80 * MS, 300 * MS),
    ("ingest_dispatch", "t1", 90 * MS, 95 * MS),
    ("ingest_dispatch", "t1", 200 * MS, 210 * MS),
    ("sink_absorb", "t2", 250 * MS, 400 * MS),
    ("ingest_dispatch", "t1", 1200 * MS, 1210 * MS),  # after the window
]


def ctx(**over):
    run = {"mode": "backfill", "window_s": 10.0, "trace": reading(), "trace_ticks": 2,
           "program_spans": list(SPANS),
           "stages": {"parser_sweep": {"busy_s": 0.5, "events": 10, "released": 900, "failed": 1},
                      "state_save": {"busy_s": 0.25, "events": 2, "bytes": 4096},
                      "ingest_dispatch": {"busy_s": 0.1, "events": 7, "records": 300,
                                          "slots": 1024}}}
    run.update(over)
    return run


def test_unattributed_idle_share():
    # busy 0-100 and 500-650 ms; spans open 80-400 ms: 400-500 and
    # 650-1000 ms are idle with no span open: 45%
    assert ps.unattributed_idle_share(reading(), SPANS) == pytest.approx(45.0)
    assert ps.unattributed_idle_share(reading(), []) is None


def test_idle_gaps_by_stage_names_the_innermost_span_per_thread():
    gaps = ps.idle_gaps_by_stage(reading(), SPANS)
    # gaps: 100-500 (mid 300: worker_feed on t1 ends there, sink_absorb on
    # t2), 650-1000 (mid 825: nothing open)
    assert [g[0] for g in gaps] == ["sink_absorb+worker_feed", "none"]
    assert [round(g[1], 6) for g in gaps] == [0.4, 0.35]
    inner = ps.idle_gaps_by_stage(reading(), [("worker_feed", "t1", 0.0, 1000 * MS),
                                              ("tick_emit", "t1", 200 * MS, 400 * MS)], 1)
    assert inner == [["tick_emit", pytest.approx(0.4)]]


def test_ingest_device_ms_per_dispatch_in_the_window():
    # 150 ms of ingest program over the two dispatches that start in the window
    assert ps.ingest_device_ms(reading(), SPANS) == pytest.approx(75.0)
    assert ps.ingest_device_ms(reading(), SPANS[:1]) is None


@pytest.fixture(scope="module")
def cell():
    return Cell(load_benchmark(), "prod380.backfill")


def test_new_readers_on_a_hand_built_context(cell):
    readers = cell.readers()
    got = {name: readers[name](ctx()) for name in NEW}
    assert got == {
        "sweep_busy_share.backfill": pytest.approx(5.0),
        "save_busy_share.backfill": pytest.approx(2.5),
        "ingest_dispatch_busy_share.backfill": pytest.approx(1.0),
        "ingest_fill_share.backfill": pytest.approx(100.0 * 300 / 1024),
        "ingest_device_ms.backfill": pytest.approx(75.0),
        "idle_unattributed_share.backfill": pytest.approx(45.0),
    }


def test_new_readers_find_nothing_in_a_program_without_them(cell, monkeypatch):
    """The parent program has no such clocks or spans: every new reader gives
    None and none raises."""
    readers = cell.readers()
    bare = ctx(stages={"parser_scan": {"busy_s": 1.0, "events": 3}})
    bare.pop("program_spans")
    monkeypatch.setattr(ps, "program_stages", lambda: None)
    assert {name: readers[name](bare) for name in NEW} == dict.fromkeys(NEW)
    assert {name: readers[name](ctx(trace=None, program_spans=None)) for name in NEW[4:]} \
        == dict.fromkeys(NEW[4:])


def test_spans_for_takes_only_the_trace_whose_window_matches(monkeypatch, tmp_path):
    monkeypatch.setattr(ps, "_trace_files", lambda: ["a.xplane.pb", "b.xplane.pb"])
    found = {"a.xplane.pb": ((0.0, 5.0), [("gc", "t", 1.0, 2.0)]),
             "b.xplane.pb": ((0.0, 1000 * MS), SPANS)}
    monkeypatch.setattr(ps, "program_spans", lambda path, names: found[path])
    run = ctx()
    run.pop("program_spans")
    assert ps.spans_for(run) == SPANS
    assert ps.spans_for(run) is run["_program_spans"]  # read once per run
    stale = ctx(trace=TraceReading((1.0, 2.0), {}, {}, []))
    stale.pop("program_spans")
    assert ps.spans_for(stale) is None


PROGRAM_TRACE = os.path.join(os.path.dirname(__file__), "data", "program.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    from chipbench.harness.trace import read_trace

    reading = read_trace(PROGRAM_TRACE, ("feed", "settle"))
    window, spans = ps.program_spans(PROGRAM_TRACE, ps.program_stages())
    return reading, window, spans


def test_program_spans_of_a_recorded_trace(recorded):
    reading, window, spans = recorded
    assert tuple(window) == tuple(reading.window)  # the harness's ``window`` span
    names = sorted(name for name, _t, _a, _b in spans)
    assert names == ["ingest_dispatch"] * 3 + ["sink_absorb"] * 3 + ["worker_feed"] * 3
    threads = {name: {t for n, t, _a, _b in spans if n == name} for name in set(names)}
    assert threads["ingest_dispatch"] == threads["worker_feed"]
    assert len(threads["sink_absorb"]) >= 1
    assert not threads["sink_absorb"] & threads["worker_feed"]  # its own thread
    sink = [(b - a) * 1e-9 for n, _t, a, b in spans if n == "sink_absorb"]
    assert all(0.03 <= s < 0.035 for s in sink)


def test_recorded_idle_gaps_by_stage_and_unattributed_share(recorded):
    reading, _window, spans = recorded
    gaps = ps.idle_gaps_by_stage(reading, spans, 6)
    # each round: ~30 ms idle under the sink's span, then ~20 ms with none
    assert [name for name, _s in gaps] == ["sink_absorb"] * 3 + ["none"] * 3
    assert all(0.03 <= s < 0.035 for _n, s in gaps[:3])
    assert all(0.02 <= s < 0.03 for _n, s in gaps[3:])
    # the harness's own spans name the same gaps ``settle`` and ``other``
    assert [name for name, _s in reading.idle_gaps(6)] == ["settle"] * 3 + ["other"] * 3
    share = ps.unattributed_idle_share(reading, spans)
    assert 100.0 * 0.06 / reading.window_s <= share < 100.0 * 0.075 / reading.window_s


def test_recorded_ingest_device_ms(recorded):
    reading, _window, spans = recorded
    ingest_s = reading.modules["jit_engine_ingest"]
    assert ps.ingest_device_ms(reading, spans) == pytest.approx(1000.0 * ingest_s / 3)
    assert 0 < ingest_s < reading.busy_s
