"""Drive the served path, ``StandalonePipeline``, through a cell's traffic.

``Run`` is what every traffic mode shares: the cell's pipeline built on
the program's defaults and the configuration file, the fleet's logs
generated from the seed, the stage clocks, the profiler, and the drain
and shutdown after the window. What a mode does in set-up and in the
window lives in ``modes/<mode>.py``, named by the traffic file's
``mode``: a module with ``setup(run)``, ``window(run)`` and ``fed(run)``
(what was fed: lines per file, the feed sequence number of every line,
and the monotonic time of each sequence number). A new mode is a new file.

Where the configuration file has a ``history`` section, ``build_pipeline``
first writes the saved day (``harness/history.py``) as the engine's resume
file, which the worker restores at boot through its own path.

Every mode warms up on the cell's own data, so every program the window
runs has been compiled and run before it opens. Afterwards the fed prefix
is drained through and the pipeline shut down, so the check sees
everything that was due.
"""

from __future__ import annotations

import contextlib
import gc
import os
import sqlite3
import time
from typing import Dict, List, Optional

import numpy as np

from .fleet import generate_fleet
from .history import History, history_params, write_resume
from .history import generate as generate_history
from .reference import WindowSpec

SPANS = ("feed", "settle")  # the harness's host spans in a trace
TRACE_S = 10.0  # seconds of the window the profiler records


def merge(base: dict, over: dict) -> dict:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            merge(base[k], v)
        else:
            base[k] = v
    return base


def pipeline_config(cell_cfg: dict, work: str) -> dict:
    """The program's defaults, the configuration file's ``program`` settings,
    and every path inside the run's work directory."""
    from apmbackend_tpu.config import default_config

    cfg = merge(default_config(), cell_cfg["program"])
    cfg["logDir"] = os.path.join(work, "logs")
    eng = cfg["tpuEngine"]
    eng["resumeFileFullPath"] = os.path.join(work, "engine.resume.npz")
    eng["checkpointDir"] = os.path.join(work, "ckpt")
    eng["checkpointChainDir"] = os.path.join(work, "ckpt.chain")
    eng["heapSnapshotDir"] = os.path.join(work, "logs")
    cfg["streamProcessAlerts"]["alertsResumeFileFullPath"] = os.path.join(work, "alerts.resume")
    db = cfg["streamInsertDb"]
    db["dbFileFullPath"] = os.path.join(work, "apm.db")
    db["bufferResumeFileFullPath"] = os.path.join(work, "db.resume")
    pt = cfg["streamParseTransactions"]
    pt["tailPauseFileFullPath"] = os.path.join(work, "PAUSE")
    pt["serverFromPathPattern"] = r"/([^/]+)/[^/]+$"  # the server's directory
    pt["serverPathComponentIndex"] = None
    return cfg


def resume_files(cfg: dict) -> List[str]:
    """The files the program's timers save its state to."""
    return [cfg["tpuEngine"]["resumeFileFullPath"],
            cfg["streamProcessAlerts"]["alertsResumeFileFullPath"],
            cfg["streamInsertDb"]["bufferResumeFileFullPath"]]


def window_spec(cfg: dict) -> WindowSpec:
    st = cfg["streamCalcStats"]
    return WindowSpec(int(st["windowSizeInIntervals"]), int(st["bufferSizeInIntervals"]),
                      int(st["intervalLengthInSeconds"]))


def zscore_lags(cfg: dict) -> List[tuple]:
    return [(int(d["LAG"]), float(d["THRESHOLD"]), float(d["INFLUENCE"]))
            for d in cfg["streamCalcZScore"]["defaults"]]


def stat_rows_committed(db_path: str) -> int:
    """FullStat rows a reader of the sink's database sees (rowids grow by
    one per row: the table only takes inserts)."""
    if not os.path.exists(db_path):
        return 0
    con = sqlite3.connect(db_path, timeout=30)
    try:
        row = con.execute("SELECT max(rowid) FROM stats").fetchone()
    except sqlite3.OperationalError:
        return 0
    finally:
        con.close()
    return int(row[0] or 0)


def stage_busy() -> Dict[str, dict]:
    """Every stage clock of the program (``StageClock``), as it reads now."""
    from apmbackend_tpu.obs.attrib import get_attrib

    return get_attrib().stage_table()


def stage_delta(before: Dict[str, dict], after: Dict[str, dict]) -> Dict[str, dict]:
    """Each stage clock's numbers over an interval, for every stage."""
    out = {}
    for stage, a in after.items():
        b = before.get(stage, {})
        out[stage] = {k: v - b.get(k, 0) for k, v in a.items()
                      if isinstance(v, (int, float)) and not isinstance(v, bool)}
    return out


def ticks_so_far() -> int:
    return int(stage_busy().get("tick_dispatch", {}).get("events", 0))


class GcClock:
    """Seconds the process spent in Python's garbage collector."""

    def __init__(self):
        self.seconds = 0.0
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds += time.perf_counter() - self._t
            self._t = None

    def close(self) -> None:
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)


class Tracer:
    """The profiler over the first ``seconds`` of the window of a traced
    run, with the harness's ``window`` span on the host plane."""

    def __init__(self, out_dir: Optional[str], seconds: float):
        self.out_dir = out_dir
        self.seconds = seconds
        self.until = None
        self._span = None
        self.path = None
        self.ticks0 = 0
        self.ticks = 0

    def start(self, now: float) -> None:
        if self.out_dir is None:
            return
        import jax

        # no Python tracer: it records every Python call, and this host
        # path is Python; the harness's TraceAnnotations are host TraceMes
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("window")
        self._span.__enter__()
        self.until = now + self.seconds
        self.ticks0 = ticks_so_far()

    def maybe_stop(self, now: float, force: bool = False) -> None:
        if self._span is None or (now < self.until and not force):
            return
        import glob

        import jax

        self.ticks = ticks_so_far() - self.ticks0
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"), recursive=True))
        self.path = found[-1] if found else None


def span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class Feeder:
    """Round-robin chunked replay of a directory of logs, cut at the last
    newline of each chunk, the partial tail carried to the file's next
    chunk (``ReplayDriver.feed_dir``), with the bytes fed per file and the
    time of each chunk kept."""

    def __init__(self, parser, paths: List[str], chunk_bytes: int):
        self.parser = parser
        self.chunk = chunk_bytes
        self.handles = [(p, open(p, "rb")) for p in sorted(paths)]
        self.tails = {p: b"" for p in paths}
        self.fed = {p: 0 for p in paths}
        self.chunks = {p: [] for p in paths}  # (bytes fed so far, feed sequence number)
        self.times: List[float] = []  # monotonic time of each feed sequence number
        self.live = list(self.handles)
        self.pos = 0
        self.lines = 0

    def step(self, trace_spans: bool = False) -> bool:
        """Feed the next file's next chunk; False once every file is done."""
        while self.live:
            if self.pos >= len(self.live):
                self.live = [h for h in self.live if h[1] is not None]
                self.pos = 0
                continue
            path, fh = self.live[self.pos]
            self.pos += 1
            blob = fh.read(self.chunk)
            if not blob:
                fh.close()
                self.live[self.pos - 1] = (path, None)
                continue
            blob = self.tails[path] + blob
            cut = blob.rfind(b"\n")
            if cut < 0:
                self.tails[path] = blob
                continue
            self.chunks[path].append((self.fed[path] + cut + 1, len(self.times)))
            self.times.append(time.monotonic())
            with span("feed", trace_spans):
                self.lines += self.parser.read_lines(path, blob[:cut + 1])
            self.fed[path] += cut + 1
            self.tails[path] = blob[cut + 1:]
            return True
        return False

    def close(self) -> None:
        for _p, fh in self.handles:
            if fh is not None and not fh.closed:
                fh.close()

    def lines_fed(self, fleet) -> Dict[str, int]:
        return {p: int(np.searchsorted(fleet.files[p][0], self.fed[p], side="right"))
                for p in self.fed}

    def arrival(self, fleet) -> Dict[str, np.ndarray]:
        """Per file, the feed sequence number of the chunk that carried each line."""
        out = {}
        for p, (ends, _rel) in fleet.files.items():
            marks = np.asarray([b for b, _s in self.chunks[p]] or [0], np.int64)
            seqs = np.asarray([s for _b, s in self.chunks[p]] or [-1], np.int64)
            idx = np.minimum(np.searchsorted(marks, ends, side="left"), len(marks) - 1)
            out[p] = seqs[idx]
        return out


class Run:
    """One run of one cell: set-up, window, finish; what the check and the
    readers need is left on the object."""

    def __init__(self, cell, seed: int, seconds: float, trace_dir: Optional[str],
                 work: str, workers: int, compiles=lambda: 0):
        self.cell = cell
        self.cfg_file = cell.config
        self.traffic = cell.traffic
        self.cfg = pipeline_config(self.cfg_file, work)
        self.spec = window_spec(self.cfg)
        self.lags = zscore_lags(self.cfg)
        self.history_params = history_params(self.cfg_file, self.cfg)
        self.history: Optional[History] = None
        self.seed = seed
        self.seconds = float(seconds)
        self.work = work
        self.workers = workers
        self.compiles = compiles  # programs compiled so far in this process
        self.tracer = Tracer(trace_dir, min(self.seconds, TRACE_S))
        self.gc = GcClock()
        self.evicted: List[int] = []
        self.metrics: Dict[str, float] = {}
        self.notes: Dict[str, object] = {}
        self._ticks0 = ticks_so_far()

    def ticks(self) -> int:
        """Ticks this run's pipeline has run (the stage clocks are per process)."""
        return ticks_so_far() - self._ticks0

    # ------------------------------------------------ shared by the modes --
    def generate(self, n_tx_per_server: int, log_dir: str) -> None:
        """The fleet's logs under ``log_dir``, drawn from the seed as the
        traffic's ``records`` section says."""
        self.fleet = generate_fleet(self.cfg_file, n_tx_per_server, self.seed, log_dir,
                                    self.workers, self.traffic.get("records"))

    def build_pipeline(self, tail: bool = False) -> None:
        from apmbackend_tpu.standalone import StandalonePipeline

        cfg = self.cfg
        self.db_path = cfg["streamInsertDb"]["dbFileFullPath"]
        if self.history_params is not None:
            self.write_history()
        self.pipe = StandalonePipeline(config=cfg, tail=tail, install_signals=False)
        for rt in (self.pipe.lead, self.pipe.parser_rt, self.pipe.sink_rt, self.pipe.jmx_rt):
            rt.qm.on("overflow", lambda _queue, n: self.evicted.append(int(n)))

    def write_history(self) -> None:
        """The configuration's saved day (``harness/history.py``), drawn from
        the seed for the generated fleet's keys and written as the engine's
        resume file, which the worker restores at boot."""
        t0 = time.perf_counter()
        self.history = generate_history(self.fleet, self.fleet.engine_keys(),
                                        self.history_params, self.spec, self.lags,
                                        self.traffic.get("records"), self.seed)
        write_resume(self.cfg["tpuEngine"]["resumeFileFullPath"], self.history, self.cfg)
        self.notes["history_s"] = time.perf_counter() - t0

    def saves_between(self, t0: float, t1: float) -> int:
        """Resume files the program's timers wrote between two
        ``time.time()`` readings."""
        n = 0
        for path in resume_files(self.cfg):
            if os.path.exists(path) and t0 <= os.path.getmtime(path) <= t1:
                n += 1
        return n

    # ------------------------------------------------------------ phases --
    def setup(self) -> None:
        self.mode = self.cell.mode()
        self.mode.setup(self)

    def window(self) -> None:
        self.mode.window(self)

    def drain(self) -> None:
        """Bring everything fed through the pipeline, then read what the
        pipeline counted."""
        self.lines_fed, self.arrival, self.fed_times = self.mode.fed(self)
        self.pipe.parser.drain()
        self.pipe.drain()
        self.dropped = int(self.pipe.worker.intake_dropped) + sum(self.evicted)

    def shutdown(self) -> List[str]:
        """Stop the pipeline (it saves its state); the engine's saved tx backlog."""
        self.pipe.shutdown()
        path = self.cfg["tpuEngine"]["resumeFileFullPath"]
        with np.load(path, allow_pickle=True) as data:
            return [str(x) for x in data["pending_tx"].tolist()]

    def close(self) -> None:
        self.gc.close()
        pipe = getattr(self, "pipe", None)
        if pipe is not None:
            pipe.shutdown()
