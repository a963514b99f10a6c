"""The command's refusals, and a cell added by files alone."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from chipbench.harness.spec import BENCH_DIR, ROOT, Cell, load_benchmark

ARGS = ["--workload", "prod380.backfill", "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_command_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _digest(root):
    out = {}
    for d, _dirs, files in os.walk(root):
        for f in files:
            if "__pycache__" not in d:
                path = os.path.join(d, f)
                with open(path, "rb") as fh:
                    out[os.path.relpath(path, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(tmp_path):
    """A configuration, a traffic mix and a metric reader, each a new file,
    plus entries in BENCHMARK.json: a new cell, no existing file edited. The
    mix draws its records otherwise than any cell before (skewed services
    whose hot set moves, bursts, late lines, an anomaly) and the reader
    takes a stage clock that no reader took before; the cell is run through
    the command's own ``run_cell`` at a tiny size on the CPU."""
    from chipbench.run import layer_metrics
    from chipbench.tests.rehearsal import rehearse, shrink

    bench_dir = tmp_path / "chipbench"
    shutil.copytree(BENCH_DIR, bench_dir, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _digest(bench_dir)
    bench = load_benchmark()

    cfg = json.loads((bench_dir / "configs" / "prod380.json").read_text())
    cfg.update(name="prod760", servers=70, services_per_server=[6] * 30 + [5] * 40)
    (bench_dir / "configs" / "prod760.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "burst.json").write_text(json.dumps(
        {"mode": "backfill", "backlog_tx": 90000, "feed_after_warm_s": None, "records": {
            "services": {"zipf_s": 1.1, "active": 3, "shift_every_s": 120, "shift_by": 1},
            "rate": {"period_s": 300, "steps": [[0, 1.0], [200, 3.0], [230, 1.0]]},
            "late": {"share": 0.05, "delay_ms": [1000, 15000]},
            "anomalies": [{"servers": 1, "services": 1, "from_s": 100, "to_s": 400,
                           "elapsed_factor": 4.0}]}}))
    (bench_dir / "metrics" / "send_busy_share.burst.py").write_text(
        "from chipbench.harness.readers import busy_share\n\n\n"
        "def read(run):\n    return busy_share(run, 'transport_send')\n")
    bench["configs"].append({"name": "prod760", "source": "https://example.org/x",
                             "file": "chipbench/configs/prod760.json", "reduced": [],
                             "why": "w"})
    bench["workloads"].append({"name": "prod760.burst", "config": "prod760",
                               "traffic": "burst", "chips": 1, "why": "w"})
    next(m for m in bench["end_to_end"] if m["name"] == "backfill_records_per_s")[
        "workloads"].append("prod760.burst")
    bench["per_layer"].append({"name": "send_busy_share.burst", "unit": "%",
                               "better": "lower", "source": "program_counter",
                               "layer": "transport", "moves": "backfill_records_per_s",
                               "workloads": ["prod760.burst"]})

    cell = Cell(bench, "prod760.burst", root=str(tmp_path), bench_dir=str(bench_dir))
    assert cell.config["servers"] == 70 and cell.traffic["backlog_tx"] == 90000
    assert cell.end_to_end_names() == ["setup_s", "backfill_records_per_s"]
    assert list(cell.readers()) == ["send_busy_share.burst"]

    result, run = rehearse(shrink(cell), str(tmp_path / "work"), 2**31 + 5)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s", "backfill_records_per_s"}
    layers, _reading = layer_metrics(cell, run)
    assert list(layers) == ["send_busy_share.burst"] and layers["send_busy_share.burst"] > 0
    after = _digest(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before
