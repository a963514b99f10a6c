"""Percent of the traced window in which the chip is idle and no program stage
span is open on any host thread (the harness's own spans do not count)."""

from chipbench.harness.program_spans import spans_for, unattributed_idle_share


def read(run):
    spans = spans_for(run)
    return unattributed_idle_share(run["trace"], spans) if spans else None
