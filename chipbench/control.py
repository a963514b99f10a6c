#!/usr/bin/env python3
"""Readings of the check's numbers for the program and for its control.

    python3 chipbench/control.py --workload <name> --seconds <s> --seeds <n> [<n> ...]

For each seed it makes one run of the cell through ``run.py``'s own
``measure`` (on the chip, at the cell's own size and load), which judges
what the sink committed twice: as it stands (the program's readings,
``checks``) and with every compared row's statistics replaced by the
float64 reference's computed in bfloat16 at the same key and tick (the
control's readings, ``control``). It prints one JSON line per seed. The
benchmark's own runs never run the control; ``PERF.md`` keeps the readings
that each limit was set from.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from chipbench.harness.reference import control_row
    from chipbench.run import measure

    for seed in args.seeds:
        one = argparse.Namespace(workload=args.workload, seed=seed, seconds=args.seconds, trace=0)
        result = measure(one, time.perf_counter(), control=control_row)
        print(json.dumps(dict(workload=args.workload, seed=seed, **result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
