"""Benchmark suite runner: ``python -m benchmarks.run [--config NAME] [--all]``.

Prints one JSON result line per benchmark (same schema as bench.py). Use
``--quick`` for a smoke-sized pass (CI / CPU).

Each benchmark runs in its OWN subprocess by default (``--in-process`` to
disable): a shared process distorts later configs badly — measured podshard
at 486k in-suite vs 1.05M standalone, purely from allocator and cache
pressure left behind by the earlier 850 MB-ring configs. The subprocess
inherits the environment (JAX_PLATFORMS, XLA_FLAGS, the persistent compile
cache), so isolation changes nothing but the starting heap.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys


def _run_isolated(name: str, quick: bool) -> dict:
    cmd = [sys.executable, "-m", "benchmarks.run", "--config", name, "--in-process"]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True
    )
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                obj = json.loads(line)
                if isinstance(obj, dict) and "metric" in obj:
                    return obj
            except json.JSONDecodeError:
                continue
    raise RuntimeError(
        f"no result line (rc={proc.returncode}): {proc.stderr[-400:]}"
    )


def _trajectory_row(res: dict) -> dict:
    """One consolidated-index entry per result line: the headline triple
    plus the ISSUE 17 attribution certifications, WITHOUT the full details
    blob (the per-bench JSON lines keep that)."""
    row = {k: res[k] for k in ("metric", "value", "unit", "vs_baseline")
           if k in res}
    if "error" in res:
        row["error"] = res["error"]
    d = res.get("details") or {}
    att = d.get("attribution")
    if not isinstance(att, dict) and isinstance(d.get("frames"), dict):
        att = d["frames"].get("attribution")
    if isinstance(att, dict):
        row["attribution"] = {
            k: att[k]
            for k in ("expected_bottleneck", "bottleneck", "certified",
                      "verdict", "overhead_pct", "within_gate")
            if k in att
        }
    qp = d.get("queryplane")
    if isinstance(qp, dict):
        # ISSUE 20: the query-plane serving certification — routing/merge/
        # degraded-drill verdicts plus the headline serving numbers
        serving = qp.get("serving") or {}
        drill = qp.get("degraded_drill") or {}
        row["queryplane"] = {
            "certified": qp.get("certified"),
            "routing_exact": (qp.get("routing") or {}).get("exact"),
            "merge_bitequal": qp.get("merge_bitequal"),
            "qps_cached": (serving.get("cache_on") or {}).get("qps"),
            "qps_uncached": (serving.get("cache_off") or {}).get("qps"),
            "cache_hit_ratio": serving.get("cache_hit_ratio"),
            "drill_p95_ms": drill.get("p95_ms"),
            "drill_zero_5xx": drill.get("zero_5xx"),
            "drill_partial_stale": bool(drill.get("post_kill_partial"))
            and bool(drill.get("post_kill_stale")),
        }
    return row


def _write_trajectory(rows, quick: bool) -> str:
    """Write the consolidated ``BENCH_TRAJECTORY.json`` index at the repo
    root (the BENCH_r09.json location convention): every run refreshes one
    machine-readable summary of the latest suite pass instead of leaving
    the trajectory scattered across stdout logs."""
    import os
    import time

    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_TRAJECTORY.json")
    body = {
        "generated_unixtime": round(time.time(), 3),
        "quick": bool(quick),
        "results": rows,
    }
    with open(out, "w", encoding="utf-8") as fh:
        json.dump(body, fh, indent=1)
        fh.write("\n")
    return out


def main(argv=None) -> int:
    from . import REGISTRY

    ap = argparse.ArgumentParser(prog="benchmarks.run")
    ap.add_argument("--config", choices=sorted(REGISTRY), action="append",
                    help="benchmark(s) to run (default: --all)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--quick", action="store_true", help="smoke-sized shapes")
    ap.add_argument("--in-process", action="store_true",
                    help="run in this process (no per-config isolation)")
    ap.add_argument("--repeat", type=int, default=None,
                    help="run each config N times, report the median-by-value "
                    "run (default: 3 for podshard, 1 otherwise)")
    ap.add_argument("--fleet", type=int, metavar="N", default=None,
                    help="run ONLY the fleet spine bench with N shards and "
                    "record the certified row into BENCH_r09.json "
                    "(the pod-scale acceptance artifact)")
    args = ap.parse_args(argv)

    if args.fleet is not None:
        # the fleet bench orchestrates its own subprocesses (one per
        # shard), so it runs in-process here; the result row is both
        # printed and recorded as the BENCH_r09 certification artifact
        from .bench_fleet import run as fleet_run

        res = fleet_run(quick=args.quick, shards=args.fleet)
        line = json.dumps(res)
        print(line, flush=True)
        import os

        out = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "BENCH_r09.json")
        with open(out, "w", encoding="utf-8") as fh:
            fh.write(line + "\n")
        _write_trajectory([_trajectory_row(res)], args.quick)
        d = res.get("details", {})
        slo = d.get("slo", {})
        att = d.get("attribution", {})
        print(f"attribution: bottleneck={att.get('bottleneck')} "
              f"certified={att.get('certified')} ({att.get('verdict')})",
              file=sys.stderr, flush=True)
        print(f"slo: compliant={slo.get('compliant')} "
              f"fast={slo.get('fast_burning')} slow={slo.get('slow_burning')} "
              f"({slo.get('recorder_rows')} rows recorded over "
              f"{slo.get('recorder_scrapes')} scrapes)",
              file=sys.stderr, flush=True)
        qp = d.get("queryplane", {})
        qp_drill = qp.get("degraded_drill", {})
        print(f"queryplane: certified={qp.get('certified')} "
              f"routing_exact={qp.get('routing', {}).get('exact')} "
              f"merge_bitequal={qp.get('merge_bitequal')} "
              f"drill(5xx={qp_drill.get('five_xx')} "
              f"p95={qp_drill.get('p95_ms')}ms "
              f"partial={qp_drill.get('post_kill_partial')})",
              file=sys.stderr, flush=True)
        ok = bool(d.get("meets_1m_aggregate")) and bool(d.get("meets_100ms_budget")) \
            and bool(d.get("rebalance", {}).get("zero_loss")) \
            and bool(d.get("rebalance", {}).get("conformance_clean")) \
            and bool(qp.get("certified"))
        return 0 if ok else 1

    names = args.config or sorted(REGISTRY)
    if args.in_process:
        # only the process that runs the benches touches the device: the
        # isolating parent stays off JAX so its children can take the chip
        from apmbackend_tpu.utils.compile_cache import use_compile_cache

        use_compile_cache()
    failed = 0
    traj_rows = []
    for name in names:
        # the podshard margin is the one number the project is named after,
        # and single runs on a loaded one-core host swing ~±20% (VERDICT r5
        # weak 3): report the MEDIAN of three subprocess runs so the
        # north-star claim survives a busy machine. --repeat overrides;
        # median requires isolation (in-process runs share heap distortion).
        repeat = args.repeat if args.repeat is not None else (
            3 if name == "podshard" and not args.in_process and not args.quick else 1
        )
        try:
            runs = []
            for _ in range(max(repeat, 1)):
                if args.in_process:
                    runs.append(REGISTRY[name](quick=args.quick))
                else:
                    runs.append(_run_isolated(name, args.quick))
            runs.sort(key=lambda r: r.get("value", 0.0))
            res = runs[len(runs) // 2]
            if len(runs) > 1:
                res.setdefault("details", {})["median_of"] = {
                    "runs": len(runs),
                    "values": [r.get("value") for r in runs],
                }
            print(json.dumps(res), flush=True)
            traj_rows.append(_trajectory_row(res))
        except Exception as e:  # one failing bench must not hide the others
            failed += 1
            err = {"metric": name, "error": f"{type(e).__name__}: {e}"}
            print(json.dumps(err), file=sys.stderr, flush=True)
            traj_rows.append(_trajectory_row(err))
    out = _write_trajectory(traj_rows, args.quick)
    print(f"trajectory index: {out}", file=sys.stderr, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
