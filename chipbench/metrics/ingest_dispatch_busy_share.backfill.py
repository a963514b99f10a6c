"""Percent of the window spent on the host side of ingest dispatches: the pad,
the column fill and the ingest call (StageClock ingest_dispatch)."""

from chipbench.harness.readers import busy_share


def read(run):
    return busy_share(run, "ingest_dispatch")
