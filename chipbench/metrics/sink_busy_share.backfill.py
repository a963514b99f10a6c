"""Percent of the window spent in the sink's inserts (StageClock sink_absorb)."""

from chipbench.harness.readers import busy_share


def read(run):
    return busy_share(run, "sink_absorb")
