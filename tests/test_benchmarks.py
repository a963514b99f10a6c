"""Benchmark suite smoke tests: every BASELINE.json config bench runs in
quick mode on the virtual CPU mesh and returns the standard result schema."""

import json
import sys

import pytest

sys.path.insert(0, ".")  # benchmarks/ lives at repo root beside the package

from benchmarks import REGISTRY  # noqa: E402

REQUIRED_KEYS = {"metric", "value", "unit", "vs_baseline", "details"}


@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_bench_quick(name):
    res = REGISTRY[name](quick=True)
    assert REQUIRED_KEYS <= set(res)
    if name == "pallas":
        # off-TPU the kernel bench verifies parity but reports speedup 0
        # (timing needs hardware); the parity check raising IS the test
        assert res["details"]["parity"] == "exact"
    else:
        assert res["value"] > 0
        if res["vs_baseline"] is None:  # no per-chip comparison off the chip
            assert not res["unit"].endswith("/chip"), res["unit"]
        else:
            assert res["vs_baseline"] > 0
    json.dumps(res)  # must be JSON-serializable (the wire contract)


def test_registry_covers_all_five_configs():
    # the five BASELINE.json configs plus the pallas hardware-proof,
    # dispatch-floor, and fleet-spine extras
    assert set(REGISTRY) == {
        "replay", "rolling", "jmx", "podshard", "multiwindow", "pallas",
        "dispatch", "fleet",
    }


def test_runner_cli(capsys):
    from benchmarks.run import main

    rc = main(["--config", "rolling", "--quick"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    res = json.loads(out[0])
    assert res["metric"] == "rolling_baseline_throughput"
