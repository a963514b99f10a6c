"""The backfill mode rehearsed small on the CPU, the control, and the
faults the check has to catch."""

import pytest

from chipbench.harness.reference import control_row
from chipbench.tests.rehearsal import failing, rehearse, tiny_cell

SEED = 2**31 + 11  # larger than 32 signed bits hold


@pytest.fixture(scope="module")
def backfill(tmp_path_factory):
    cell = tiny_cell("prod380.backfill", backlog_tx=120_000, feed_after_warm_s=1.0)
    return rehearse(cell, str(tmp_path_factory.mktemp("backfill")), SEED, control=control_row)


def test_backfill_rehearsal_is_correct(backfill):
    result, run = backfill
    assert result["correct"], result["checks"]
    assert result["checks"]["rows_compared"][0] > 100
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
