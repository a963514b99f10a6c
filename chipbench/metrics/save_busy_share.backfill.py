"""Percent of the window spent writing resume and checkpoint files (StageClock
state_save); None in a window that holds no save."""

from chipbench.harness.readers import busy_share


def read(run):
    return busy_share(run, "state_save")
