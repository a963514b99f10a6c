"""The backfill mode rehearsed small on the CPU, the control, and the
faults the check has to catch; the same with a saved day restored."""

import copy

import numpy as np
import pytest

from chipbench.harness.reference import control_row
from chipbench.tests.rehearsal import failing, rehearse, tiny_cell

SEED = 2**31 + 11  # larger than 32 signed bits hold

# lags longer than the tiny backlog (168 intervals), as the upstream's 360 and
# 8640 are longer than a run: only a restored list can be full
HISTORY_LAGS = [{"LAG": 180, "THRESHOLD": 20.0, "INFLUENCE": 0.1},
                {"LAG": 200, "THRESHOLD": 15.0, "INFLUENCE": 0.0}]
SHORT_LAGS = [{"LAG": 12, "THRESHOLD": 20.0, "INFLUENCE": 0.1},
              {"LAG": 40, "THRESHOLD": 15.0, "INFLUENCE": 0.0}]
# x3 elapsed on one service of one server: signals on both lags
ANOMALY = [{"servers": 1, "services": 1, "from_s": 10, "to_s": 70, "elapsed_factor": 3.0}]


@pytest.fixture(scope="module")
def backfill(tmp_path_factory):
    cell = tiny_cell("prod380.backfill", backlog_tx=120_000, feed_after_warm_s=1.0)
    return rehearse(cell, str(tmp_path_factory.mktemp("backfill")), SEED, control=control_row)


def test_backfill_rehearsal_is_correct(backfill):
    result, run = backfill
    assert result["correct"], result["checks"]
    assert result["checks"]["rows_compared"][0] > 100
    assert result["checks"]["bands_compared"] == [0, 0]  # no history: no list fills
    assert result["metrics"]["backfill_records_per_s"]["value"] > 0
    assert list(result)[-1] == "checks"
    assert run.notes["fullstat_rows_committed"] > 0 and run.notes["slices"]
    assert run.notes["feed_s"] >= 1.0
    assert run.stages["parser_scan"]["events"] > 0 and run.stages["sink_absorb"]["events"] > 0


def test_control_is_not_correct(backfill):
    """The reference in bfloat16 in the program's place fails stat_gap (and
    its rounded tpm the exact count_gap)."""
    result, run = backfill
    assert result["control_correct"] is False
    assert "stat_gap" in failing(run.control_checks), result["control"]


def _fault(monkeypatch, tmp_path, patch):
    from apmbackend_tpu.pipeline import PipelineDriver

    patch(monkeypatch, PipelineDriver)
    cell = tiny_cell("prod380.backfill", backlog_tx=120_000)
    result, run = rehearse(cell, str(tmp_path), SEED + 1, seconds=3.0)
    assert result["correct"] is False
    return run.checks


def test_fault_state_unchanged(monkeypatch, tmp_path):
    """Ingest returns the state it was given: no record reaches the window."""
    def patch(mp, cls):
        mp.setattr(cls, "_ingest_arrays", lambda self, rows, labels, elaps: None)

    checks = _fault(monkeypatch, tmp_path, patch)
    assert "count_gap" in failing(checks), checks


def test_fault_half_batch(monkeypatch, tmp_path):
    """Half of every batch left out before it reaches the device."""
    def patch(mp, cls):
        inner = cls._ingest_arrays

        def half(self, rows, labels, elaps):
            n = (len(rows) + 1) // 2
            return inner(self, rows[:n], labels[:n], elaps[:n])

        mp.setattr(cls, "_ingest_arrays", half)

    checks = _fault(monkeypatch, tmp_path, patch)
    assert "count_gap" in failing(checks), checks


def test_fault_answer_altered(monkeypatch, tmp_path):
    """Each FullStat row's average 2% high where the row is made."""
    from apmbackend_tpu.entries import FullStatEntry

    inner = FullStatEntry.__post_init__

    def altered(self):
        inner(self)
        if self.average == self.average:  # not NaN
            self.average *= 1.02

    def patch(mp, _cls):
        mp.setattr(FullStatEntry, "__post_init__", altered)

    checks = _fault(monkeypatch, tmp_path, patch)
    assert failing(checks) == ["stat_gap"], checks


def history_cell(lags=HISTORY_LAGS, **engine):
    """The tiny backfill restored from a saved day as long as its longest lag,
    ended 36 intervals (window + buffer) before the backlog."""
    cell = tiny_cell("prod380.backfill", backlog_tx=120_000, feed_after_warm_s=1.0)
    intervals = max(d["LAG"] for d in lags)
    cell.config["history"] = {"intervals": intervals, "outage_intervals": 36}
    program = cell.config["program"]
    program["streamCalcZScore"] = {"defaults": copy.deepcopy(lags)}
    program["tpuEngine"].update(engine)
    cell.traffic = dict(cell.traffic, records=dict(cell.traffic["records"], anomalies=ANOMALY))
    return cell


@pytest.fixture(scope="module")
def restored(tmp_path_factory):
    return rehearse(history_cell(), str(tmp_path_factory.mktemp("restored")), SEED + 2)


def test_history_rehearsal_is_correct(restored):
    """The worker restores the day at boot; bands and signals are compared
    from the first tick, against lists seeded from the same series."""
    result, run = restored
    assert result["correct"], result["checks"]
    assert result["checks"]["bands_compared"][1] == 1
    assert result["checks"]["bands_compared"][0] >= result["checks"]["rows_compared"][0] > 100
    assert run.notes["reference"]["signals"] > 0  # the anomaly, against full lists
    assert run.notes["history_s"] > 0
    assert run.pipe.worker.driver._latest_label > run.history.last_label


def _history_fault(monkeypatch, tmp_path, patch, lags=HISTORY_LAGS):
    from apmbackend_tpu.pipeline import PipelineDriver
    from chipbench.harness.drive import Run

    patch(monkeypatch, PipelineDriver, Run)
    result, run = rehearse(history_cell(lags), str(tmp_path), SEED + 3, seconds=3.0)
    assert result["correct"] is False
    return run.checks


def test_history_fault_file_removed(monkeypatch, tmp_path):
    """No resume file at the build: the program starts empty, no list of it
    fills, and no band is compared."""
    def patch(mp, _driver, _run):
        mp.setattr("chipbench.harness.drive.write_resume", lambda *_a: None)

    checks = _history_fault(monkeypatch, tmp_path, patch)
    assert checks["bands_compared"]["value"] == 0 and "bands_compared" in failing(checks)


def test_history_fault_ring_rotated(monkeypatch, tmp_path):
    """The program's rings restored one slot round: it drops the newest value
    first and reads a wrong last value."""
    def patch(mp, driver, _run):
        inner = driver._install_resume_data

        def rotated(self, data, source):
            data = {k: np.roll(v, 1, axis=2) if k.startswith("z") and k.endswith("_values")
                    else v for k, v in data.items()}
            return inner(self, data, source)

        mp.setattr(driver, "_install_resume_data", rotated)

    checks = _history_fault(monkeypatch, tmp_path, patch)
    assert "zscore_mismatch" in failing(checks), checks


def test_history_fault_reference_other_seed(monkeypatch, tmp_path):
    """The reference's lists drawn from another seed than the program's."""
    from chipbench.harness.history import generate

    def patch(mp, _driver, run_cls):
        inner = run_cls.write_history

        def other(self):
            inner(self)
            self.history = generate(self.fleet, self.fleet.engine_keys(), self.history_params,
                                    self.spec, self.lags, self.traffic.get("records"),
                                    self.seed + 1)

        mp.setattr(run_cls, "write_history", other)

    checks = _history_fault(monkeypatch, tmp_path, patch)
    assert "zscore_mismatch" in failing(checks), checks


def test_sliding_aggregates_after_restore_fail(tmp_path):
    """A program fault the check finds (PERF.md, Open questions): at lags the
    tiny run outlasts, the engine's sliding z-score aggregates (its default,
    ``zscoreVariancePass`` auto) leave bands on a restored list whose only
    valid entry is left (the upstream gives none: zero variance), and drift
    once a large value has left a list. When the program is mended this
    reads correct, and the test is to be turned round."""
    result, run = rehearse(history_cell(SHORT_LAGS), str(tmp_path), SEED + 4)
    assert result["correct"] is False
    assert failing(run.checks) == ["zscore_mismatch"], run.checks


def test_exact_path_after_restore_is_correct(tmp_path):
    """The second witness: the same cell on the program's exact ring pass
    (``zscoreVariancePass`` two) agrees with the reference."""
    cell = history_cell(SHORT_LAGS, zscoreVariancePass="two")
    result, run = rehearse(cell, str(tmp_path), SEED + 4)
    assert result["correct"], result["checks"]
    assert result["checks"]["bands_compared"][0] > 100
