"""Record the small profiler trace with program stage spans that
tests/test_program_spans.py reduces.

Run on a TPU host, from the checkout root:

    python3 chipbench/tests/record_program_trace.py [OUT]

Inside a harness ``window`` span, three rounds of: a harness ``feed`` span
holding the program's ``worker_feed`` and ``ingest_dispatch`` spans around
a program named ``engine_ingest``; a harness ``settle`` span in which a
second thread holds a ``sink_absorb`` span for 30 ms; a program named
``engine_core_tick`` outside any span; and 20 ms with no span open. So
each round holds a ~30 ms device-idle gap named by ``sink_absorb`` and a
~20 ms one that no program span names. The spans are the program's own
(``StageClock.span``), so the file holds what a traced benchmark run
holds. The trace is copied to ``chipbench/tests/data/program.xplane.pb``
and what it holds is printed, so that the test's expectations can be read
off it. ``OUT`` names another place for the copy.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(HERE, "data", "program.xplane.pb")


def main(argv) -> int:
    out = argv[0] if argv else OUT
    import jax
    import jax.numpy as jnp

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from apmbackend_tpu.obs.attrib import AttributionPlane

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"record_program_trace: no TPU (JAX found {dev.platform})", file=sys.stderr)
        return 1

    @jax.jit
    def engine_ingest(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def engine_core_tick(x):
        return jnp.sum(jnp.sin(x) * 3.0, axis=0)

    plane = AttributionPlane(module="record", enabled=True)
    feed, ingest, sink = (plane.clock(s) for s in ("worker_feed", "ingest_dispatch", "sink_absorb"))

    def absorb():
        with sink.span():
            time.sleep(0.03)

    x = jnp.ones((1024, 1024), jnp.float32)
    engine_ingest(x).block_until_ready()
    engine_core_tick(x).block_until_ready()
    work = tempfile.mkdtemp(prefix="record_program_trace_", dir=os.path.join(HERE, "data"))
    try:
        jax.profiler.start_trace(work)
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("feed"), feed.span(), ingest.span():
                    engine_ingest(x).block_until_ready()
                with jax.profiler.TraceAnnotation("settle"):
                    t = threading.Thread(target=absorb, name="sink")
                    t.start()
                    t.join()
                engine_core_tick(x).block_until_ready()
                time.sleep(0.02)
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(work, "**", "*.xplane.pb"), recursive=True)
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        shutil.copyfile(found[0], out)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    from jax.profiler import ProfileData

    pd = ProfileData.from_file(out)
    for plane_ in pd.planes:
        print(f"plane {plane_.name!r}")
        for line in plane_.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:12]:
                print(f"    {ev.name!r} start_ns={ev.start_ns} dur_ns={ev.duration_ns}")
    print(f"clocks {plane.stage_table()}")
    print(f"kind {dev.device_kind!r}; wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
