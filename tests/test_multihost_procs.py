"""TWO-PROCESS jax.distributed smoke: the production init_distributed wiring
(parallel/multihost.py) exercised across real process boundaries.

Every other "multi-host" test runs as one process on the virtual mesh; this
one launches two OS processes that rendezvous through a coordinator, form a
4-device mesh (2 local devices each, Gloo collectives on the CPU backend),
and push distinct per-host batches through the all-to-all exchange — the
closest this environment can get to the reference's multi-process topology
(apm_manager.js:333-342 role) without pod hardware.
"""

import os
import socket
import subprocess
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_WORKER = os.path.join(_HERE, "mp_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_distributed_exchange():
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(port), str(pid)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
            cwd=_HERE,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        # re-communicate after kill to retrieve the HUNG worker's buffered
        # output — it is the diagnostic that matters
        for p in procs:
            try:
                out, _ = p.communicate(timeout=10)
                outs.append(out)
            except Exception:
                pass
        pytest.fail("two-process smoke timed out:\n" + "\n".join(o[-3000:] for o in outs))
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} rc={p.returncode}\n{out[-3000:]}"
        assert f"MP_SMOKE_OK proc={pid}" in out, out[-3000:]
