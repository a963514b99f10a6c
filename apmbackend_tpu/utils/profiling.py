"""Heap snapshots + live profiler server (tracing/profiling parity, SURVEY.md §5.1).

The reference gives every module three profiling affordances: heapdump +
node-oom-heapdump (timestamped .heapsnapshot on demand and on OOM,
stream_parse_transactions.js:55-61), a per-module V8 inspector port for live
attachment (apm_manager.js:263-267), and perf_hooks micro-timing (DBStats).
TPU-native equivalents:

- :func:`heap_snapshot` — a JSON snapshot combining tracemalloc's top
  allocation sites, gc generation stats, process RSS, and per-device XLA
  memory stats (``device.memory_stats()`` — the on-TPU "heap"), written
  timestamped like ``<name>-<ts>.heapsnapshot.json``.
- :func:`install` — per-module wiring: starts tracemalloc, dumps on SIGUSR2
  (on-demand heapdump; SIGUSR1 is already the requestGC channel), hooks
  sys.excepthook to auto-dump on MemoryError (node-oom-heapdump role), and
  starts ``jax.profiler.start_server(port)`` — the live-inspection port: a
  perfetto/tensorboard-attachable trace server, the XLA analog of
  ``--inspect=<heapInspectPort>``.

Micro-timing parity lives in utils/counters.DBStats; cache introspection in
ingest.parser.cache_stats.
"""

from __future__ import annotations

import gc
import json
import os
import signal
import sys
import time
import tracemalloc
from typing import Optional

_TOP_SITES = 40


NO_DEVICE = "no device in this process"


def loaded_jax():
    """The ``jax`` module if this process already imported it, else None.

    Only the device owner (the worker) imports jax. A diagnostic in the
    parser, sink, JMX or manager process must not be what initializes the
    TPU there: that process would take the chip from the worker, or fail
    against it."""
    return sys.modules.get("jax")


def _device_memory_stats():
    jax = loaded_jax()
    if jax is None:
        return NO_DEVICE
    try:
        out = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            out.append({"device": str(d), **{k: int(v) for k, v in stats.items()}})
        return out
    except Exception:
        return []


def _process_rss_kb() -> Optional[int]:
    try:
        with open("/proc/self/status", "r", encoding="ascii") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return None


def heap_snapshot(out_dir: str, name: str, *, logger=None) -> Optional[str]:
    """Write ``<name>-<ts>.heapsnapshot.json``; returns the path (None on
    failure — a diagnostics writer must never take the module down)."""
    try:
        snap = {
            "ts": time.strftime("%Y%m%d-%H%M%S"),
            "rss_kb": _process_rss_kb(),
            "gc": [dict(s) for s in gc.get_stats()],
            "gc_objects": len(gc.get_objects()),
            "devices": _device_memory_stats(),
        }
        if tracemalloc.is_tracing():
            current, peak = tracemalloc.get_traced_memory()
            snap["traced_current_bytes"] = current
            snap["traced_peak_bytes"] = peak
            stats = tracemalloc.take_snapshot().statistics("lineno")[:_TOP_SITES]
            snap["top_sites"] = [
                {
                    "site": str(s.traceback),
                    "size_bytes": s.size,
                    "count": s.count,
                }
                for s in stats
            ]
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{name}-{snap['ts']}.heapsnapshot.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(snap, fh, indent=1)
        if logger:
            logger.warning(f"Heap snapshot written: {path}")
        return path
    except Exception as e:  # pragma: no cover - diagnostics must not kill
        if logger:
            logger.error(f"Heap snapshot failed: {e}")
        return None


class Profiling:
    """Per-module profiling harness (install() wires everything)."""

    def __init__(self, name: str, config: dict, *, logger=None):
        self.name = name
        self.logger = logger
        self.out_dir = config.get("heapSnapshotDir", "logs")
        self.profiler_port = config.get("profilerPort")  # None = no server
        self.trace_allocations = bool(config.get("traceAllocations", False))
        self._prev_excepthook = None
        self._server_started = False

    def install(self, *, install_signal: bool = True) -> None:
        if self.trace_allocations and not tracemalloc.is_tracing():
            tracemalloc.start()
        if install_signal and hasattr(signal, "SIGUSR2"):
            try:
                signal.signal(signal.SIGUSR2, lambda *_: self.dump())
            except ValueError:
                pass  # not the main thread (embedded/standalone satellites)
        # node-oom-heapdump role: snapshot on the way down from MemoryError.
        # One hook per process: in single-process (standalone) topology four
        # runtimes share the interpreter and must not stack four dumps.
        if not getattr(sys.excepthook, "_apm_oom_hook", False):
            self._prev_excepthook = sys.excepthook

            def hook(exc_type, exc, tb):
                if issubclass(exc_type, MemoryError):
                    self.dump()
                (self._prev_excepthook or sys.__excepthook__)(exc_type, exc, tb)

            hook._apm_oom_hook = True
            sys.excepthook = hook
        if self.profiler_port:
            self.start_profiler_server(int(self.profiler_port))

    def start_profiler_server(self, port: int) -> bool:
        """The live-inspection port (--inspect parity): a JAX/XLA profiler
        server that TensorBoard/perfetto can attach to while the module runs."""
        jax = loaded_jax()
        if jax is None:
            if self.logger:
                self.logger.warning(f"No profiler server on :{port}: {NO_DEVICE}")
            return False
        try:
            jax.profiler.start_server(port)
            self._server_started = True
            if self.logger:
                self.logger.info(f"JAX profiler server listening on :{port}")
            return True
        except Exception as e:
            if self.logger:
                self.logger.error(f"Could not start profiler server on :{port}: {e}")
            return False

    def dump(self) -> Optional[str]:
        return heap_snapshot(self.out_dir, self.name, logger=self.logger)

    def uninstall(self) -> None:
        if self._prev_excepthook is not None:
            sys.excepthook = self._prev_excepthook
            self._prev_excepthook = None
