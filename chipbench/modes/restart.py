"""Restart: the backfill after a restore of a saved day, a closed loop.

The configuration's ``history`` is written as the engine's resume file
and the worker restores it at boot (``drive.Run.build_pipeline``); then
the set-up, the window and what was fed are the backfill's
(``modes/backfill.py``), with the same traffic parameters. Added:

- the restore happens in the set-up, and the window holds none, so the
  ``state_restore`` clock's delta over the set-up goes to
  ``run.stages["state_restore"]``, where the readers find it;
- the ``tick_emit`` counters ``banded`` and ``signals`` (FullStat rows
  that carried a band, a non-zero signal) go to ``run.notes``: over the
  window, and over the set-up and the window (``*_to_window_end``), beside
  the check's count of the reference's signals over the whole run.

A program without those clocks or counters leaves them out.
"""

from __future__ import annotations

from chipbench.harness.drive import stage_busy, stage_delta
from chipbench.modes import backfill

EMIT_COUNTERS = ("banded", "signals")


def setup(run) -> None:
    run.stages0 = stage_busy()
    backfill.setup(run)
    run.restore = stage_delta(run.stages0, stage_busy()).get("state_restore")


def window(run) -> None:
    w0 = stage_busy()
    backfill.window(run)
    if run.restore is not None:
        run.stages["state_restore"] = run.restore
        run.notes["state_restore"] = run.restore
    w1 = stage_busy()
    in_window = stage_delta(w0, w1).get("tick_emit", {})
    to_end = stage_delta(run.stages0, w1).get("tick_emit", {})
    for name in EMIT_COUNTERS:
        if name in in_window:
            run.notes[name] = in_window[name]
            run.notes[name + "_to_window_end"] = to_end[name]


def fed(run):
    return backfill.fed(run)
