"""The chip the run holds: its check, its compile clock and its memory peak."""

from __future__ import annotations

import os
import subprocess
from typing import List

from .peaks import UnknownChip, peaks
from .spec import ROOT


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(n_chips: int) -> List:
    """The first ``n_chips`` TPU devices; exits non-zero with no result
    when JAX finds another platform, fewer chips, or a kind of chip with no
    entry in the peak table. There is no CPU path."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"chipbench: no TPU (JAX found {devices[0].platform}); "
                     "the benchmark measures the chip only")
    if len(devices) < n_chips:
        raise NoChip(f"chipbench: {n_chips} chips asked for, {len(devices)} visible")
    try:
        peaks(devices[0].device_kind)
    except UnknownChip as e:
        raise NoChip(f"chipbench: {e}") from None
    return devices[:n_chips]


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
    return max(peaks)


class CompileClock:
    """Sums JAX's backend-compile events (copied from chip_smoke.py): the
    compile seconds of set-up, and the compiles inside the window, which
    should be none."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, fun_name: str = "?", **_kw):
        if name.endswith("backend_compile_duration"):
            self.seconds += secs
            self.count += 1
            self.names.append(fun_name)


def ensure_native() -> str:
    """Build ``native/`` into ``native/build`` when that is absent (the
    first run of a checkout builds it, later runs reuse it); returns the
    build directory. A failed build raises: the served path's tailer,
    parser and decoder are native."""
    build = os.path.join(ROOT, "native", "build")
    if not os.path.isdir(build):
        subprocess.run(["make", "-j8", "-C", os.path.join(ROOT, "native")],
                       check=True, stdout=subprocess.DEVNULL)
    from apmbackend_tpu import native

    native.use_build(build)
    return build
