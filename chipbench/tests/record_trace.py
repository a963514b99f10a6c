"""Record the small profiler trace that tests/test_trace.py reduces.

Run on a TPU host, from the checkout root:

    python3 chipbench/tests/record_trace.py [OUT]

Two named programs run back to back with host gaps between them, each
inside a harness-style ``TraceAnnotation``, so the recorded file holds
device ops, XLA module names and host spans on one clock. The trace is
copied to ``chipbench/tests/data/small.xplane.pb``; what it holds is
printed so that the test's expectations can be read off it. ``OUT``
names another place for the copy.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "data", "small.xplane.pb")


def main(argv) -> int:
    out = argv[0] if argv else OUT
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"record_trace: no TPU (JAX found {dev.platform})", file=sys.stderr)
        return 1

    @jax.jit
    def bench_square(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def bench_sum(x):
        return jnp.sum(jnp.sin(x) * 3.0, axis=0)

    x = jnp.ones((1024, 1024), jnp.float32)
    bench_square(x).block_until_ready()
    bench_sum(x).block_until_ready()
    work = tempfile.mkdtemp(prefix="record_trace_", dir=os.path.join(HERE, "data"))
    try:
        jax.profiler.start_trace(work)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("feed"):
                bench_square(x).block_until_ready()
            with jax.profiler.TraceAnnotation("settle"):
                time.sleep(0.02)
            bench_sum(x).block_until_ready()
            time.sleep(0.01)
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(work, "**", "*.xplane.pb"), recursive=True)
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        shutil.copyfile(found[0], out)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(out)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:6]:
                print(f"    {ev.name!r} start_ns={ev.start_ns} dur_ns={ev.duration_ns} "
                      f"stats={dict(list(ev.stats)[:6])}")
    print(f"kind {dev.device_kind!r}; wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
