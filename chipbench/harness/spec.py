"""Find a cell's parts by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of
its own (``configs/<config>.json``, ``traffic/<traffic>.json``). The mix
names its mode, the code that drives it (``modes/<mode>.py``), and each
per-layer metric is a reader of its own (``metrics/<metric>.py``, a
function ``read(run) -> float | None``). A later cell, mix, mode or metric
is a new file and a new entry: nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    """The benchmark's files do not describe the cell asked for."""


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SpecError(f"no BENCHMARK.json at {root}")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _json(path: str) -> dict:
    if not os.path.exists(path):
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


class Cell:
    """One workload of BENCHMARK.json with its configuration, traffic and
    metric readers."""

    def __init__(self, bench: dict, workload: str, root: str = ROOT,
                 bench_dir: str = BENCH_DIR):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SpecError(f"no workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
        self.workload = cells[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        entry = configs[self.workload["config"]]
        self.config = _json(os.path.join(root, entry["file"]))
        self.traffic_name = self.workload["traffic"]
        self.traffic = _json(os.path.join(bench_dir, "traffic", f"{self.traffic_name}.json"))
        self.end_to_end = [m for m in bench["end_to_end"] if self._applies(m)]
        self.per_layer = [m for m in bench["per_layer"] if self._applies(m)]
        self._bench_dir = bench_dir
        self._root = root

    def _applies(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def _module(self, kind: str, name: str):
        path = os.path.join(self._bench_dir, kind, f"{name}.py")
        if not os.path.exists(path):
            raise SpecError(f"no {kind} file {os.path.relpath(path, self._root)}")
        mod_spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod

    def mode(self):
        """The traffic's mode: a module with ``setup``, ``window`` and ``fed``."""
        return self._module("modes", self.traffic["mode"])

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        return self._module("metrics", metric).read

    def readers(self) -> Dict[str, Callable[[dict], Optional[float]]]:
        return {m["name"]: self.reader(m["name"]) for m in self.per_layer}

    def units(self) -> Dict[str, str]:
        return {m["name"]: m["unit"] for m in self.end_to_end + self.per_layer}

    def end_to_end_names(self) -> List[str]:
        return [m["name"] for m in self.end_to_end]
