#!/usr/bin/env python3
"""The benchmark of the APM pipeline's served path on the chip.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. It looks the cell up in BENCHMARK.json,
requires a TPU (there is no CPU path), builds ``native/`` if absent,
places JAX's compile cache inside the checkout, generates the cell's logs
from the seed, warms the pipeline up on them, measures for ``--seconds``,
drains, checks what the sink committed against the float64 reference, and
prints one JSON line last on stdout. With ``--trace 1`` the metrics are
the cell's per-layer ones, read from the stage clocks and a profiler trace
of the window's first seconds.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORK = os.path.join(ROOT, ".benchwork")  # gitignored: logs, sink, traces of one run

# the limit of each number compared; stat_gap (ms) lies between the program's
# widest reading over its seeds (0.0) and the bfloat16 control's narrowest
# (11.6), nearer the control (PERF.md, "How correct is decided");
# bands_compared is held to 1 where the configuration has a history
# (``limits``), since only then can a z-score list be full
LIMITS = {
    "lost_records": 0, "extra_records": 0, "missing_rows": 0, "count_gap": 0,
    "null_mismatch": 0, "stat_gap": 6.0, "zscore_mismatch": 0, "alerts_unexpected": 0,
    "rows_compared": 1, "bands_compared": 0,
}
SAMPLED_KEYS = 512


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def layer_metrics(cell, run) -> dict:
    """The cell's per-layer readings; a reader that finds nothing is left out."""
    from chipbench.harness.trace import read_trace

    reading = None
    if run.tracer.path is not None:
        from chipbench.harness.drive import SPANS

        reading = read_trace(run.tracer.path, SPANS)
    ctx = {"mode": run.traffic["mode"], "window_s": run.window_s, "stages": run.stages,
           "trace": reading, "trace_ticks": run.tracer.ticks}
    out = {}
    for name, read in cell.readers().items():
        value = read(ctx)
        if value is not None:
            out[name] = value
    return out, reading


def limits(run) -> dict:
    """``LIMITS`` for this run's configuration."""
    return dict(LIMITS, bands_compared=1 if run.history_params is not None else 0)


def measure(args, t_start: float, control=None) -> dict:
    """One run of the cell on the chip; its result line as a dict. With
    ``control`` (a row function such as ``reference.control_row``) the result
    also holds the check's readings with the control in the program's place."""
    from chipbench.harness.device import require_tpu
    from chipbench.harness.spec import Cell, load_benchmark

    cell = Cell(load_benchmark(ROOT), args.workload)
    devices = require_tpu(cell.chips)
    return run_cell(cell, args, t_start, devices, control=control)[0]


def run_cell(cell, args, t_start: float, devices, control=None, work: str = WORK,
             workers: int = min(8, os.cpu_count() or 1)):
    """Everything a run does once it holds its devices: (result, run)."""
    from chipbench.harness.device import CompileClock, describe, ensure_native, peak_bytes

    chip_s = time.perf_counter() - t_start
    clock = CompileClock()
    ensure_native()
    from apmbackend_tpu.utils.compile_cache import use_compile_cache

    cache = use_compile_cache()
    say(f"{cell.name} seed {args.seed} on {describe(devices)}; compile cache {cache}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    trace_dir = os.path.join(work, "trace") if args.trace else None

    from chipbench.harness.check import AT_LEAST, judge, sample_keys
    from chipbench.harness.drive import Run

    run = Run(cell, args.seed, args.seconds, trace_dir, work, workers=workers,
              compiles=lambda: clock.count)
    try:
        run.setup()
        setup_s = time.perf_counter() - t_start
        compiles0, compile_s0 = clock.count, clock.seconds
        run.window()
        compiles_in_window = clock.count - compiles0
        run.drain()
        memory_peak = peak_bytes(devices)
        layers, reading = layer_metrics(cell, run) if args.trace else ({}, None)
        pending = run.shutdown()
    finally:
        run.close()
    say(f"set-up {setup_s:.3f} s (to the chip {chip_s:.3f} s, compile {compile_s0:.3f} s, "
        f"{compiles0} programs); "
        f"{compiles_in_window} compiles inside the window {clock.names[compiles0:]}; "
        f"notes {json.dumps(run.notes)}")

    keys = sample_keys(run.fleet.engine_keys(), SAMPLED_KEYS, args.seed)
    expected = run.fleet.expected(run.lines_fed, run.arrival)

    def check(row_fn=None) -> dict:
        return judge(expected, run.db_path, pending, keys, run.spec, run.lags,
                     fed_times=run.fed_times, dropped=run.dropped, limits=limits(run),
                     control=row_fn, history=run.history)

    t_check = time.perf_counter()
    checks = check()
    run.notes["check_s"] = time.perf_counter() - t_check
    failed = checks.pop("_failed")
    run.notes["reference"] = checks.pop("_reference")
    say(f"check took {run.notes['check_s']:.3f} s; reference {json.dumps(run.notes['reference'])}")
    run.checks = checks
    correct = all(c["ok"] for c in checks.values())

    units = cell.units()
    if args.trace:
        values = layers
    else:
        values = dict(run.metrics, setup_s=setup_s)
    metrics = {name: {"value": values[name], "unit": units[name]}
               for name in (cell.end_to_end_names() if not args.trace else values)
               if name in values}
    device = dict(describe(devices), memory_peak_bytes=memory_peak)
    result = {"correct": correct, "attempted": len(expected), "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace and reading is not None:
        device["busy_s"] = reading.busy_s
        device["window_s"] = reading.window_s
        result["breakdown"] = {"device_ops": reading.top_programs(10),
                               "idle_gaps": reading.idle_gaps(10)}
    if control is not None:
        cchecks = check(control)
        cchecks.pop("_failed")
        cchecks.pop("_reference")
        run.control_checks = cchecks
        result["control_correct"] = all(c["ok"] for c in cchecks.values())
        result["control"] = {name: c["value"] for name, c in cchecks.items()}
    result["checks"] = {name: [c["value"], c["limit"]] for name, c in checks.items()}
    for name, c in checks.items():
        rel = ">=" if name in AT_LEAST else "<="
        say(f"check {name} {c['value']} (limit {rel} {c['limit']}) {'ok' if c['ok'] else 'FAIL'}")
    return result, run


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse(argv)
    try:
        result = measure(args, t_start)
    except SystemExit:
        raise
    except Exception as e:  # no result line: the run failed
        import traceback

        traceback.print_exc()
        say(f"run failed: {type(e).__name__}: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
