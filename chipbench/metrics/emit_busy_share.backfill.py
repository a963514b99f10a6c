"""Percent of the window spent in emission and alert rules (StageClock tick_emit)."""

from chipbench.harness.readers import busy_share


def read(run):
    return busy_share(run, "tick_emit")
