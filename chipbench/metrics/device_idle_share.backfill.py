"""Percent of the traced window in which no operation ran on the chip."""

from chipbench.harness.readers import device_idle_share


def read(run):
    return device_idle_share(run)
