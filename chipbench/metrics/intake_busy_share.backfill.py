"""Percent of the window spent in the worker's intake feed (StageClock worker_feed)."""

from chipbench.harness.readers import busy_share


def read(run):
    return busy_share(run, "worker_feed")
