"""Persistent XLA compile-cache retest (ROADMAP housekeeping, ISSUE 7).

Round 6 root-caused the suite's flaky segfault to the persistent compile
cache's cpu_aot_loader path miscompiling buffer donation for fused
(single-program read+write) steps, and disabled the cache suite-wide
(tests/conftest.py). This is the standing retest: run the exact hazardous
shape — two PipelineDrivers stepping the same donated fused program in one
process — in a subprocess with the cache ENABLED, cold and then warm, and
compare the final state against a cache-disabled oracle.

Retested 2026-08 on jax 0.4.37: NOT reproducible — oracle, cold-cache and
warm-cache runs are bit-identical, and the fused-tick parity suite passes
cold+warm with the cache on. The cache stays off on the CPU backend
(apmbackend_tpu/utils/compile_cache.py) because its only upside there is
compile time, but this test keeps the question answered on every jax bump: if it starts failing, the
miscompile is back — re-quarantine before trusting any cached run.
"""

import json
import os
import subprocess
import sys

import pytest

_REPRO = r"""
import os, sys, json
sys.path.insert(0, os.getcwd())  # the repo root (subprocess cwd)
import numpy as np
os.environ["JAX_PLATFORMS"] = "cpu"
cache_dir = sys.argv[1]
import jax
if cache_dir:
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
from apmbackend_tpu.config import default_config
from apmbackend_tpu.pipeline import PipelineDriver

cfg = default_config()
cfg["tpuEngine"]["serviceCapacity"] = 64
cfg["tpuEngine"]["samplesPerBucket"] = 32
cfg["tpuEngine"]["tickExecutor"] = "fused"  # the donated read+write program
cfg["streamCalcZScore"]["defaults"] = [{"LAG": 6, "THRESHOLD": 3.0, "INFLUENCE": 0.1}]
base = 170_000_000
lines = [
    f"tx|j|s{i%9}|c{t}-{i}|1|{(base+t)*10000-7}|{(base+t)*10000+i}|{40+i%200}|Y"
    for t in range(12) for i in range(30)
]
# TWO drivers: the round-6 corruption needed a second driver re-loading the
# same cached executable in-process (shared cpu_aot_loader artifacts)
d1 = PipelineDriver(cfg, capacity=64)
d2 = PipelineDriver(cfg, capacity=64)
out = {}
for name, d in (("d1", d1), ("d2", d2)):
    d.feed_csv_batch(lines)
    d.flush()
    out[name] = {
        "counts": np.asarray(d.state.stats.counts).tolist(),
        "sums": np.nansum(np.asarray(d.state.stats.sums, dtype=np.float64)),
        "ring": np.nansum(np.asarray(d.state.zscores[0].values, dtype=np.float64)),
        "fill": np.asarray(d.state.zscores[0].fill).tolist(),
        "pos": int(np.asarray(d.state.zscores[0].pos)),
    }
print(json.dumps(out))
"""


def _run(cache_dir, tmp_path):
    script = tmp_path / "repro.py"
    script.write_text(_REPRO)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(script), cache_dir],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_persistent_cache_donation_parity(tmp_path):
    cache = str(tmp_path / "xla-cache")
    os.makedirs(cache)
    oracle = _run("", tmp_path)
    cold = _run(cache, tmp_path)
    assert os.listdir(cache), "cache dir empty: the repro never hit the cache path"
    warm = _run(cache, tmp_path)
    assert oracle["d1"] == oracle["d2"]  # in-process agreement first
    assert cold == oracle, "cache COLD run diverged: cpu_aot_loader miscompile is back"
    assert warm == oracle, "cache WARM run diverged: cpu_aot_loader miscompile is back"
