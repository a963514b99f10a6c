"""Rehearse chip_smoke.py on the CPU at a tiny size.

``chip_smoke.main`` refuses anything but a TPU; its phase functions take
their sizes as arguments and run anywhere. They run here in a subprocess
with x64 off, the numeric mode of the chip (conftest turns x64 on for the
test process itself).
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ONE_CHIP = r"""
import json, os, sys
import chip_smoke as cs
work = sys.argv[1]
pieces = cs.build_native(os.path.join(work, "native-build"))
replay = cs.replay_phase(os.path.join(work, "replay"), servers=2, services=5,
                         transactions=20000, intervals=48, capacity=64,
                         sampled=8, seed=3)
rc = cs.demo_phase(os.path.join(work, "demo"))
print("REHEARSAL " + json.dumps({"pieces": pieces, "replay": replay, "demo_rc": rc}))
"""

_FOUR_CHIPS = r"""
import json, jax
import chip_smoke as cs
res = cs.sharded_phase(jax.devices()[:4], services=64, ticks=8, per_row=3, seed=1)
print("REHEARSAL " + json.dumps(res))
"""


def _run(code, *args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="0",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    proc = subprocess.run([sys.executable, "-c", code, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("REHEARSAL ")]
    assert proc.returncode == 0 and lines, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(lines[-1][len("REHEARSAL "):])


def test_one_chip_phases(tmp_path):
    out = _run(_ONE_CHIP, str(tmp_path))
    assert all(out["pieces"].values()), out["pieces"]
    replay = out["replay"]
    # 2 servers x 5 names x 2 record kinds
    assert replay["keys"] == replay["keys_with_rows"] == 20
    # audit-trail records of non-Provider services go to the DB only
    assert 0 < replay["ingested"] < replay["records"]
    assert replay["tx_rows"] + replay["tx_pending"] == replay["records"]
    assert replay["oracle_rows_with_tx"] > 0
    assert out["demo_rc"] == 0


def test_four_chip_phase_on_virtual_devices():
    out = _run(_FOUR_CHIPS, devices=4)
    assert out["rows_per_device"] == {str(i): 16 for i in range(4)}
    assert out["rows_compared"] == 64 * 8


def test_main_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
