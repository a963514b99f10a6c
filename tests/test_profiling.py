"""Profiling harness (utils/profiling.py): heap snapshots, OOM hook,
module-runtime wiring. The profiler server itself is only smoke-tested (port
bind is environment-dependent)."""

import json
import os
import signal
import sys

import pytest

from apmbackend_tpu.utils.profiling import Profiling, heap_snapshot


def test_heap_snapshot_contents(tmp_path):
    path = heap_snapshot(str(tmp_path), "worker")
    assert path is not None and os.path.exists(path)
    assert os.path.basename(path).startswith("worker-")
    assert path.endswith(".heapsnapshot.json")
    with open(path) as fh:
        snap = json.load(fh)
    assert snap["gc_objects"] > 0
    assert "devices" in snap and isinstance(snap["devices"], list)
    assert snap["rss_kb"] is None or snap["rss_kb"] > 0


def test_snapshot_includes_tracemalloc_sites(tmp_path):
    p = Profiling("m", {"heapSnapshotDir": str(tmp_path), "traceAllocations": True})
    p.install(install_signal=False)
    try:
        hog = [bytearray(4096) for _ in range(100)]  # noqa: F841 - make allocations
        path = p.dump()
        with open(path) as fh:
            snap = json.load(fh)
        assert snap["traced_current_bytes"] > 0
        assert len(snap["top_sites"]) > 0
    finally:
        p.uninstall()


def test_memoryerror_hook_dumps_and_chains(tmp_path):
    seen = []
    prev = sys.excepthook
    sys.excepthook = lambda *a: seen.append(a)
    p = Profiling("oom", {"heapSnapshotDir": str(tmp_path)})
    p.install(install_signal=False)
    try:
        sys.excepthook(MemoryError, MemoryError("boom"), None)
        dumps = [f for f in os.listdir(tmp_path) if f.startswith("oom-")]
        assert len(dumps) == 1
        assert len(seen) == 1  # chained to the previous hook
        # non-OOM exceptions do not dump
        sys.excepthook(ValueError, ValueError("x"), None)
        dumps = [f for f in os.listdir(tmp_path) if f.startswith("oom-")]
        assert len(dumps) == 1
    finally:
        p.uninstall()
        sys.excepthook = prev


@pytest.mark.skipif(not hasattr(signal, "SIGUSR2"), reason="no SIGUSR2")
def test_sigusr2_dump_via_module_runtime(tmp_path):
    from apmbackend_tpu.config import default_config
    from apmbackend_tpu.runtime.module_base import ModuleRuntime

    cfg = default_config()
    cfg["logDir"] = str(tmp_path)
    rt = ModuleRuntime("streamCalcStats", config=cfg, install_signals=True)
    try:
        os.kill(os.getpid(), signal.SIGUSR2)
        import time

        deadline = time.time() + 3
        while time.time() < deadline:
            if any(".heapsnapshot.json" in f for f in os.listdir(tmp_path)):
                break
            time.sleep(0.05)
        assert any(".heapsnapshot.json" in f for f in os.listdir(tmp_path))
    finally:
        rt.profiling.uninstall()


def test_profiler_server_start(tmp_path):
    p = Profiling("srv", {"heapSnapshotDir": str(tmp_path)})
    ok = p.start_profiler_server(19377)
    # jax profiler server may be unavailable in some builds; only assert the
    # call is safe and reports a boolean
    assert ok in (True, False)


def test_no_device_outside_the_device_owner(tmp_path):
    """A process that never imported jax (parser, sink, JMX, manager)
    answers /profile and heap snapshots without touching JAX: importing it
    there would initialize the TPU and take the chip from the worker."""
    import subprocess

    code = f"""
import json, sys
from apmbackend_tpu.obs.exporter import TelemetryServer
from apmbackend_tpu.utils.profiling import NO_DEVICE, heap_snapshot
exp = TelemetryServer(profile_dir={str(tmp_path)!r}, module="parser")
status, _, body = exp._handle_profile({{"ms": ["1"]}})
assert status == 200 and json.loads(body)["trace_error"] == NO_DEVICE, body
with open(heap_snapshot({str(tmp_path)!r}, "parser")) as fh:
    assert json.load(fh)["devices"] == NO_DEVICE
assert "jax" not in sys.modules
print("NO_JAX_OK")
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, timeout=120)
    assert "NO_JAX_OK" in proc.stdout, proc.stdout + proc.stderr
