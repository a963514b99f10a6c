"""The trace reduction and the peak table, on a trace recorded on a v5e
(record_trace.py: two programs, ``feed`` and ``settle`` host spans)."""

import os

import pytest

from chipbench.harness import peaks
from chipbench.harness.trace import clip, module_name, read_trace, union

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_union_and_clip():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert module_name("jit_bench_sum(12949177827598488603)") == "jit_bench_sum"


def test_reduce_recorded_trace():
    r = read_trace(TRACE, ("feed", "settle"))
    assert list(r.busy) == ["/device:TPU:0"]
    # six program runs: three of each, 19.2 us and 27.8 us
    names = dict(r.top_programs())
    assert set(names) == {"jit_bench_square", "jit_bench_sum"}
    assert names["jit_bench_square"] == pytest.approx(3 * 19.2e-6, rel=0.02)
    assert names["jit_bench_sum"] == pytest.approx(3 * 27.8e-6, rel=0.02)
    # busy is the union of op intervals: never more than the programs' time
    assert 0 < r.busy_s <= sum(names.values()) * 1.0001
    assert r.window_s > 0.1  # three rounds of 20 ms + 10 ms host gaps
    assert 0.99 < r.idle_share() < 1.0
    # no ``window`` span in this trace: the window runs from the first host
    # event to the last, so the two longest gaps are start_trace's and
    # stop_trace's own; the next three are the 20 ms ``settle`` sleeps
    gaps = r.idle_gaps(5)
    assert [name for name, _s in gaps] == ["other", "other", "settle", "settle", "settle"]
    assert all(0.02 <= secs < 0.025 for _n, secs in gaps[2:])


def test_peak_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flop_per_s"] == 197e12
    with pytest.raises(peaks.UnknownChip):
        peaks.peaks("TPU v4")
