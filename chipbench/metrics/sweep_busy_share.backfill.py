"""Percent of the window spent in the parser's need-number sweep timer (StageClock parser_sweep)."""

from chipbench.harness.readers import busy_share


def read(run):
    return busy_share(run, "parser_sweep")
