"""Device busy milliseconds per tick in the traced window."""

from chipbench.harness.readers import tick_device_ms


def read(run):
    return tick_device_ms(run)
