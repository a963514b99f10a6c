"""A cell shrunk to a size the CPU runs in seconds, driven through
``run.py``'s own ``run_cell``: everything the command does after its look
for a chip."""

from __future__ import annotations

import argparse
import copy
import json
import os
import time

from chipbench.harness.spec import BENCH_DIR, Cell, load_benchmark
from chipbench.run import run_cell

TINY = {"servers": 3, "services_per_server": 4, "tx_per_bucket": 238}


def tiny_cell(workload: str, traffic_file: str = None, bench: dict = None, **traffic) -> Cell:
    """``workload`` shrunk to TINY; ``traffic_file`` puts another mix of
    ``traffic/`` under it (a mix that no cell uses yet)."""
    cell = Cell(bench or load_benchmark(), workload)
    if traffic_file is not None:
        with open(os.path.join(BENCH_DIR, "traffic", f"{traffic_file}.json")) as fh:
            cell.traffic = json.load(fh)
    shrink(cell)
    cell.traffic = dict(cell.traffic, **traffic)
    return cell


def shrink(cell: Cell) -> Cell:
    cell.config = copy.deepcopy(cell.config)
    cell.config.update(TINY)
    cell.config["program"]["tpuEngine"]["serviceCapacity"] = 64
    # no 36 s of warm feeding for the parser's parked records to come due:
    # the tiny backlog would not last, and the rehearsal needs no steady state
    cell.traffic = dict(cell.traffic, feed_after_warm_s=None)
    return cell


def rehearse(cell: Cell, work: str, seed: int, seconds: float = 4.0, control=None):
    """One tiny run on the CPU: (result line, run)."""
    import jax

    args = argparse.Namespace(workload=cell.name, seed=seed, seconds=seconds, trace=0)
    return run_cell(cell, args, time.perf_counter(), jax.devices("cpu")[:1], control=control,
                    work=work, workers=2)


def failing(checks: dict) -> list:
    return [name for name, c in checks.items() if not c["ok"]]
