"""Backfill: catch-up after an outage, a closed loop.

The backlog is written in set-up. The replay feeds 64 KiB chunks
round-robin over the files, as ``ReplayDriver.feed_dir`` does, and after
each chunk waits until the pipeline is idle (``StandalonePipeline.
_settle``, the pipeline's own replay pacing). Feeding stops when the
window closes. The window's number is the records its chunks complete per
second; the FullStat rows the sink committed meanwhile are a note.

Traffic parameters (``traffic/<mix>.json``):

- ``backlog_tx``: transactions in the backlog, over all servers;
- ``feed_after_warm_s``: the warm-up first runs until two ticks have
  run and no program has compiled for ``WARM_QUIET_CHUNKS`` chunks (the
  intake's second batch width first shows up some chunks after the first
  ticks), then goes on feeding for this many seconds before the window
  opens (null: none). The parser parks every record that carries no SOAP
  account (the CommonTiming pairs) until its 30 s need-number TTL runs
  out on the wall clock, so the pipeline does its steady mix of work only
  from 30 s after feeding at full speed began; counting from the end of
  the compiles, a run that compiles cold feeds as much before its window
  as one that loads every program from the cache. The program's timers
  (resume saves, intake statistics) fire at the build and every 60 s
  after; with a warm-up of a few seconds the first of them falls ~20 s
  into the window;
- ``records``: how transactions are drawn (``harness/fixture.RECORDS``).

The window's notes give the rate and the stage clocks per 5 s slice.
"""

from __future__ import annotations

import os
import time

from chipbench.harness.drive import Feeder, span, stage_busy, stage_delta, stat_rows_committed

CHUNK_BYTES = 1 << 16  # the replay's chunk (StandalonePipeline.replay)
WARM_TICKS = 2
WARM_QUIET_CHUNKS = 10
SLICE_S = 5.0
SLICE_STAGES = ("parser_scan", "worker_feed", "tick_emit", "sink_absorb", "tick_dispatch")


def setup(run) -> None:
    tr = run.traffic
    n_tx = int(tr["backlog_tx"]) // int(run.cfg_file["servers"])
    t0 = time.perf_counter()
    run.generate(n_tx, os.path.join(run.work, "fleet"))
    t1 = time.perf_counter()
    run.build_pipeline(tail=False)
    t2 = time.perf_counter()
    run.feeder = Feeder(run.pipe.parser, list(run.fleet.files), CHUNK_BYTES)
    chunks, seen, since = 0, run.compiles(), 0
    while run.ticks() < WARM_TICKS or since < WARM_QUIET_CHUNKS:
        _warm_chunk(run)
        now = run.compiles()
        since = 0 if now != seen else since + 1
        seen = now
        chunks += 1
    t3 = time.perf_counter()
    until = t3 + float(tr["feed_after_warm_s"] or 0.0)
    while time.perf_counter() < until:
        _warm_chunk(run)
        chunks += 1
    run.notes.update(warm_chunks=chunks, generate_s=t1 - t0, build_s=t2 - t1, warm_s=t3 - t2,
                     feed_s=time.perf_counter() - t3)


def _warm_chunk(run) -> None:
    if not run.feeder.step():
        raise RuntimeError("the backlog ran out during the warm-up")
    run.pipe._settle()


def _slice(run, t_prev, r_prev, s_prev, gc_prev, now, records, stages):
    dt = now - t_prev
    d = stage_delta(s_prev, stages)
    return [round(now - run.window_t0, 3), round((records - r_prev) / dt, 1),
            round(100.0 * (run.gc.seconds - gc_prev) / dt, 2),
            {s: round(100.0 * d.get(s, {}).get("busy_s", 0.0) / dt, 2) for s in SLICE_STAGES}]


def window(run) -> None:
    traced = run.tracer.out_dir is not None
    feeder, fleet = run.feeder, run.fleet
    n0 = stat_rows_committed(run.db_path)
    r0 = fleet.records_within(feeder.lines_fed(fleet))
    s0 = stage_busy()
    gc0 = run.gc.seconds
    wall0 = time.time()
    t0 = time.monotonic()
    run.window_t0 = t0
    run.tracer.start(t0)
    end = t0 + run.seconds
    now = t0
    slices, cut = [], (t0, r0, s0, gc0)
    while now < end:
        if not feeder.step(traced):
            raise RuntimeError("the backlog ran out inside the window")
        with span("settle", traced):
            run.pipe._settle()
        now = time.monotonic()
        run.tracer.maybe_stop(now)
        if now - cut[0] >= SLICE_S and now < end:
            r, s = fleet.records_within(feeder.lines_fed(fleet)), stage_busy()
            slices.append(_slice(run, *cut, now, r, s))
            cut = (now, r, s, run.gc.seconds)
    t1 = time.monotonic()
    wall1 = time.time()
    n1 = stat_rows_committed(run.db_path)
    r1 = fleet.records_within(feeder.lines_fed(fleet))
    s1 = stage_busy()
    slices.append(_slice(run, *cut, t1, r1, s1))
    run.stages = stage_delta(s0, s1)
    run.tracer.maybe_stop(t1, force=True)
    run.window_s = t1 - t0
    # closed loop: a chunk is fed only once the pipeline is idle, so a
    # record fed in the window has been parsed, ingested and ticked
    run.metrics["backfill_records_per_s"] = (r1 - r0) / run.window_s
    run.notes.update(fullstat_rows_committed=n1 - n0, records=r1 - r0, lines_fed=feeder.lines,
                     gc_s=run.gc.seconds - gc0, saves_in_window=run.saves_between(wall0, wall1),
                     slices=slices)


def fed(run):
    run.feeder.close()
    return run.feeder.lines_fed(run.fleet), run.feeder.arrival(run.fleet), run.feeder.times
