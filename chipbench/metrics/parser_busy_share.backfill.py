"""Percent of the window spent in the parser's scan (StageClock parser_scan)."""

from chipbench.harness.readers import busy_share


def read(run):
    return busy_share(run, "parser_scan")
