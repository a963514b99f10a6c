"""What the per-layer readers share. Each reader gets the run's context:
``window_s``, ``stages`` (every stage clock of the program, ``StageClock``,
with its busy seconds, events and other counts over the window),
``trace`` (a ``TraceReading`` or None), ``trace_ticks`` (ticks inside the
traced seconds). A reader that finds nothing returns None."""

from __future__ import annotations

from typing import Optional


def busy_share(run: dict, stage: str) -> Optional[float]:
    """Percent of the window the program's ``stage`` clock was busy."""
    st = run["stages"].get(stage)
    if not st or st["events"] <= 0 or run["window_s"] <= 0:
        return None
    return 100.0 * st["busy_s"] / run["window_s"]


def device_idle_share(run: dict) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the chip."""
    tr = run["trace"]
    if tr is None or tr.window_s <= 0 or tr.busy_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def tick_device_ms(run: dict) -> Optional[float]:
    """Device busy milliseconds in the traced window per tick in it."""
    tr = run["trace"]
    if tr is None or run["trace_ticks"] <= 0 or tr.busy_s <= 0:
        return None
    return 1000.0 * tr.busy_s / run["trace_ticks"]
