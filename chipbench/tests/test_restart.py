"""The restart cell: its files found by name, rehearsed small through
``modes/restart.py`` with its saved day, and what is left of the sliding
z-score faults after a restore."""

import pytest

from chipbench.harness.spec import Cell, load_benchmark
from chipbench.run import layer_metrics
from chipbench.tests.rehearsal import failing, rehearse, tiny_cell
from chipbench.tests.test_rehearsal import SEED, SHORT_LAGS, history_cell

CELL = "prod380d1.restart"


def test_restart_cell_finds_its_config_traffic_mode_and_reader():
    bench = load_benchmark()
    cell = Cell(bench, CELL)
    assert cell.chips == 1 and cell.config["name"] == "prod380d1"
    assert cell.config["history"] == {"intervals": 8640, "outage_intervals": 180}
    lags = [d["LAG"] for d in cell.config["program"]["streamCalcZScore"]["defaults"]]
    assert lags == [360, 8640]
    assert cell.traffic["mode"] == "restart" and cell.traffic["records"]["anomalies"]
    mode = cell.mode()
    assert all(callable(getattr(mode, f)) for f in ("setup", "window", "fed"))
    assert cell.end_to_end_names() == ["setup_s", "backfill_records_per_s"]
    backfill = Cell(bench, "prod380.backfill")
    # the backfill's per-layer readers, since the window is the backfill's, and the restore
    assert list(cell.readers()) == list(backfill.readers()) + ["restore_s.restart"]
    read = cell.readers()["restore_s.restart"]
    assert read({"stages": {}}) is None  # a program without the clock
    assert read({"stages": {"state_restore": {"busy_s": 0.0, "events": 0}}}) is None
    assert read({"stages": {"state_restore": {"busy_s": 5.5, "events": 2}}}) == 5.5
    # the backfill cell reads what it read before, and no restore
    assert "restore_s.restart" not in backfill.readers()


@pytest.fixture(scope="module")
def restarted(tmp_path_factory):
    cell = tiny_cell(CELL, backlog_tx=120_000, feed_after_warm_s=1.0)
    cell.traffic = dict(cell.traffic, records=dict(
        cell.traffic["records"], anomalies=[dict(cell.traffic["records"]["anomalies"][0],
                                                 servers=1, services=1)]))
    return cell, rehearse(cell, str(tmp_path_factory.mktemp("restart")), SEED + 5)


def test_restart_rehearsal_is_correct_and_reads_its_restore(restarted):
    cell, (result, run) = restarted
    assert result["correct"], result["checks"]
    assert result["checks"]["bands_compared"][0] >= result["checks"]["rows_compared"][0] > 100
    assert run.notes["reference"]["signals"] > 0
    restore = run.stages["state_restore"]
    assert restore["events"] >= 1 and restore["keys"] == len(run.fleet.engine_keys())
    assert restore["valid_slots"] > 0 and restore["bytes"] > 0
    layers, _ = layer_metrics(cell, run)
    assert layers["restore_s.restart"] == restore["busy_s"] > 0
    # the program's emitted rows: with every list full, each carries a band
    assert run.notes["banded"] > 0 and run.notes["banded_to_window_end"] >= run.notes["banded"]
    assert run.notes["signals_to_window_end"] > 0


def test_backfill_readers_read_the_restart_window_alike(restarted):
    """The restart cell's readers of the backfill's layers read its window;
    the restore before the window adds nothing to what they read."""
    cell, (_result, run) = restarted
    ctx = {"mode": run.traffic["mode"], "window_s": run.window_s, "stages": run.stages,
           "trace": None, "trace_ticks": 0}
    read = {name: r(ctx) for name, r in cell.readers().items()}
    for name in ("parser_busy_share.backfill", "intake_busy_share.backfill",
                 "sink_busy_share.backfill", "ingest_fill_share.backfill",
                 "emit_busy_share.backfill", "ingest_dispatch_busy_share.backfill"):
        assert read[name] is not None and read[name] > 0, (name, read)
    assert [n for n in read if "restore" in n] == ["restore_s.restart"]


def test_sliding_aggregates_after_restore_leave_only_drift(monkeypatch, tmp_path):
    """The tiny history run at lags 12 and 40 (``test_rehearsal.py``'s
    ``test_sliding_aggregates_after_restore_fail``) narrowed to what is left.
    The restore derives the run-length guard exactly, so no row carries a
    band where the reference has none, or none where it has one. What
    ``zscore_mismatch`` still reads is float32 drift of the sliding sums on
    the anomaly's key (bands on both sides, apart by more than the check's
    room), which a rebuild of the sums at every tick cancels: a fault of
    ``ops/zscore.py`` that is not the restore's (PERF.md, Open questions)."""
    from chipbench.harness import check

    inner = check.wire_gap
    presence = []

    def spy(have, want, digits):
        if (have is None) != (want != want):
            presence.append((have, want))
        return inner(have, want, digits)

    monkeypatch.setattr(check, "wire_gap", spy)
    result, run = rehearse(history_cell(SHORT_LAGS), str(tmp_path / "sliding"), SEED + 4)
    assert failing(run.checks) == ["zscore_mismatch"], run.checks
    assert presence == []
    result, run = rehearse(history_cell(SHORT_LAGS, zscoreRebuildEvery=1),
                           str(tmp_path / "rebuilt"), SEED + 4)
    assert result["correct"], result["checks"]
