"""The fused device pipeline + host driver.

This is the TPU-native replacement for three whole reference processes —
stream_calc_stats, stream_calc_z_score, stream_process_alerts — collapsed into
ONE jitted step function over dense state (SURVEY.md §7.2 steps 4-6). Where the
reference hops RabbitMQ between stages per message, here a 10 s tick runs:

    stats.tick  ->  wire-quantize  ->  zscore.step (per lag)  ->  alerts.eval

entirely on device, for every (server, service) row at once. The host driver
around it keeps the string<->row registry, splits incoming micro-batches at
tick boundaries (preserving the reference's stats-before-addData event order,
stream_calc_stats.js:348-370), re-orders raw tx for the DB sink via the
min-heap (stream_calc_stats.js:136-155 role), applies per-service alert
cooldowns, and snapshots/restores the full device state (resume files, §5.4).

Wire parity: ``quantize=True`` rounds avg/p75/p95 to 1 decimal and tpm to 2
before the z-score step — exactly what the reference's CSV hop does
(StatEntry.toCSVString -> parseFloat, entries.js:72) — so device FullStat
output matches a reference pipeline reading the same queues.
"""

from __future__ import annotations

import math
import os
import re
import time
from collections import deque
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .entries import FullStatEntry, StatEntry, TxEntry
from .ops import alerts as dalerts
from .ops import ewma as dewma
from .ops import stats as dstats
from .ops import zscore as dzscore
from .ops.registry import CapacityExceeded, ServiceRegistry
from .utils.heap import MinHeap

# the numeric forms whose numpy float parse == JS parseInt truncation; rows
# outside this shape fall back to js_parse_int in feed_csv_batch
_PLAIN_NUMBER = re.compile(r"^[+-]?\d+(?:\.\d+)?$")


def _pad_tier_repeat(idx: np.ndarray, *, last: bool = False) -> np.ndarray:
    """Pad a gather-index vector to the next power-of-two tier by repeating
    one element (first by default, last with ``last=True``) so the delta
    capture compiles a BOUNDED set of gather shapes instead of one per
    distinct count. Duplicated indices are harmless on both sides: the
    gather reads the same cell twice, the replay scatter writes the same
    post-state value twice."""
    n = len(idx)
    if n == 0:
        return idx
    tier = 1
    while tier < n:
        tier *= 2
    if tier == n:
        return idx
    fill = idx[-1] if last else idx[0]
    return np.concatenate([idx, np.full(tier - n, fill, idx.dtype)])


class LagSpec(NamedTuple):
    lag: int
    suppressed: bool  # lag in suppressedLags
    # median/MAD baseline instead of mean/std (ops/zscore.py ZScoreConfig
    # .robust); per-lag static — it changes the compiled program
    robust: bool = False


class EngineConfig(NamedTuple):
    stats: dstats.StatsConfig
    lags: Tuple[LagSpec, ...]
    alert_rules: Tuple[dalerts.AlertRuleConfig, ...]  # one per lag
    quantize: bool = True
    # multi-window extension (SURVEY.md §7.2 step 10): EWMA/seasonal channels
    ewma: Tuple[dewma.EwmaSpec, ...] = ()
    ewma_rules: Tuple[dalerts.AlertRuleConfig, ...] = ()  # one per channel
    # storage dtype for the z-score rings (None = stats dtype); bfloat16
    # halves the dominant HBM read per tick (ops/zscore.py ring_dtype)
    zscore_ring_dtype: Optional[jnp.dtype] = None
    # one-pass shifted variance (ops/zscore.py onepass_var); f64 parity mode
    # always keeps the exact two-pass regardless
    zscore_onepass: bool = True
    # O(1)-per-tick incremental window aggregates (ops/zscore.py sliding;
    # module docstring there). Takes precedence over onepass; inert for f64
    # parity mode and robust lags. Production default: ON.
    zscore_sliding: bool = True
    zscore_rebuild_every: int = 64
    # per-tick executor: "staged" = the multi-program read-free-writer
    # choreography (make_staged_executor), "fused" = the single/two-dispatch
    # fused tick with the staggered rebuild folded in (make_fused_step),
    # "auto" = fused while the donated-copy-prone state (sample reservoir +
    # z-score rings) fits under the fused byte budget, staged above it —
    # small shapes are dispatch-bound (the ~3-4 ms/tick floor VERDICT r5
    # flagged), huge shapes are copy-bound (XLA:CPU rewrites any big buffer
    # a single program both reads and writes, measured 736 ms/tick at the
    # 8192 x 8640 ring)
    tick_executor: str = "auto"

    @property
    def capacity(self) -> int:
        return self.stats.capacity


class EngineState(NamedTuple):
    stats: dstats.StatsState
    zscores: Tuple[dzscore.ZScoreState, ...]  # one per lag
    alert_counters: Tuple[jnp.ndarray, ...]  # [S] int32 per lag
    ewmas: Tuple[dewma.EwmaState, ...] = ()  # one per EWMA channel
    ewma_counters: Tuple[jnp.ndarray, ...] = ()  # [S] int32 per channel


class EngineParams(NamedTuple):
    """Per-row parameter vectors gathered from config (refreshed on hot reload
    or registry growth)."""

    thresholds: Tuple[jnp.ndarray, ...]  # [S] per lag
    influences: Tuple[jnp.ndarray, ...]  # [S] per lag
    hard_max_ms: jnp.ndarray  # [S]
    suppressed: jnp.ndarray  # [S] bool
    # rows that exist in the registry: gates the z-score warm-up so a
    # service first seen mid-run waits a full lag window (reference per-key
    # list-creation semantics). None = treat every row as active.
    active: Optional[jnp.ndarray] = None  # [S] bool
    # per-row EWMA-channel overrides (registry.ewma_params); empty tuples =
    # every row uses the channel spec's scalar defaults
    ewma_thresholds: Tuple[jnp.ndarray, ...] = ()  # [S] per channel
    ewma_influences: Tuple[jnp.ndarray, ...] = ()  # [S] per channel


class LagEmission(NamedTuple):
    window_avg: jnp.ndarray  # [S, 3]
    lower_bound: jnp.ndarray  # [S, 3]
    upper_bound: jnp.ndarray  # [S, 3]
    signal: jnp.ndarray  # [S, 3] int32
    trigger: jnp.ndarray  # [S] bool
    cause_bits: jnp.ndarray  # [S] int32


class TickEmission(NamedTuple):
    tpm: jnp.ndarray  # [S] (wire-rounded when quantize)
    average: jnp.ndarray  # [S, 3] = (avg, p75, p95), wire-rounded
    count: jnp.ndarray  # [S] int32
    overflowed: jnp.ndarray  # [S] bool
    lags: Tuple[LagEmission, ...]
    ewma: Tuple[LagEmission, ...] = ()  # one per EWMA/seasonal channel


def zscore_cfg(cfg: EngineConfig, spec: LagSpec) -> dzscore.ZScoreConfig:
    """The ONE place an EngineConfig lag becomes a ZScoreConfig (init, tick,
    grow, restore and the sharded spec builders all route through here so the
    variance-mode/state-shape decision cannot drift between them)."""
    return dzscore.ZScoreConfig(
        cfg.capacity, spec.lag, cfg.stats.dtype, spec.robust,
        cfg.zscore_ring_dtype, cfg.zscore_onepass,
        cfg.zscore_sliding, cfg.zscore_rebuild_every,
    )


def engine_init(cfg: EngineConfig) -> EngineState:
    S = cfg.capacity
    return EngineState(
        stats=dstats.init_state(cfg.stats),
        zscores=tuple(
            dzscore.init_state(zscore_cfg(cfg, spec)) for spec in cfg.lags
        ),
        alert_counters=tuple(jnp.zeros((S,), jnp.int32) for _ in cfg.lags),
        ewmas=tuple(dewma.init_state(S, spec, cfg.stats.dtype) for spec in cfg.ewma),
        ewma_counters=tuple(jnp.zeros((S,), jnp.int32) for _ in cfg.ewma),
    )


def _engine_tick_impl(
    state: EngineState, cfg: EngineConfig, new_label, params: EngineParams,
    evicted: Optional[Tuple[jnp.ndarray, ...]],
    stats_res: Optional[dstats.TickResult] = None,
) -> Tuple[TickEmission, EngineState, Tuple[jnp.ndarray, ...]]:
    """Shared fused-tick body. ``evicted`` selects the execution shape:

    - None: single-program mode — the stats ring advances in-program and
      sliding lags compose their ring read and write inside this program
      (dzscore.step). Used by shard_map and the compile-check entry; pays
      whole-buffer copies on XLA:CPU.
    - tuple of [S, 3] slices (one per sliding lag, in lag order; may be
      empty): STAGED mode — the stats ring arrives PRE-advanced (the host
      dispatched dstats.advance_one per new label), this program only READS
      the big buffers (window_stats is read-only; sliding lags run
      ring-free via dzscore.step_core) and returns the ring pushes; the
      caller owes the ring_write dispatches. make_engine_step wires the
      programs together so every big buffer is only ever written by an
      in-place dynamic_update_slice in a read-free program.
    """
    if stats_res is not None:
        # fully-precomputed window stats (native-percentile staging: the
        # host filled per75/per95 outside this program)
        res = stats_res
        stats_state = state.stats
    elif evicted is not None:
        res = dstats.window_stats(state.stats, cfg.stats)
        stats_state = state.stats
    else:
        res, stats_state = dstats.tick(state.stats, cfg.stats, new_label)

    if cfg.quantize:
        tpm = dstats.quantize_half_up(res.tpm, 2)
        avg = dstats.quantize_half_up(res.average, 1)
        p75 = dstats.quantize_half_up(res.per75, 1)
        p95 = dstats.quantize_half_up(res.per95, 1)
    else:
        tpm, avg, p75, p95 = res.tpm, res.average, res.per75, res.per95

    new_values = jnp.stack([avg, p75, p95], axis=1)  # [S, 3]

    lag_emissions = []
    new_zstates = []
    new_counters = []
    pushes = []
    for i, spec in enumerate(cfg.lags):
        zcfg = zscore_cfg(cfg, spec)
        if evicted is not None and zcfg.sliding_active:
            act = params.active
            if act is None:
                act = jnp.ones((cfg.capacity,), bool)
            zres, zstate, push = dzscore.step_core(
                state.zscores[i], zcfg, new_values,
                params.thresholds[i], params.influences[i], act,
                evicted[len(pushes)],
            )
            pushes.append(push)
        else:
            zres, zstate = dzscore.step(
                state.zscores[i], zcfg, new_values,
                params.thresholds[i], params.influences[i], params.active,
            )
        ares = dalerts.eval_rules(
            state.alert_counters[i],
            cfg.alert_rules[i],
            avg, p75, tpm,
            zres.signal[:, 0], zres.signal[:, 1],
            params.hard_max_ms, params.suppressed,
        )
        lag_emissions.append(
            LagEmission(
                zres.window_avg, zres.lower_bound, zres.upper_bound, zres.signal,
                ares.trigger, ares.cause_bits,
            )
        )
        new_zstates.append(zstate)
        new_counters.append(ares.counters)

    # EWMA/seasonal channels: same inputs and alert ladder, O(1) state. The
    # season slot is keyed by the *edge* label — the time the emitted stats
    # actually describe (latest - buffer - 1, stream_calc_stats.js:356) — not
    # the raw tick label.
    edge_label = jnp.asarray(new_label, jnp.int32) - (cfg.stats.buffer_sz + 1)
    ewma_emissions = []
    new_estates = []
    new_ecounters = []
    for i, espec in enumerate(cfg.ewma):
        eres, estate = dewma.step(
            state.ewmas[i], espec, new_values, edge_label,
            params.ewma_thresholds[i] if i < len(params.ewma_thresholds) else None,
            params.ewma_influences[i] if i < len(params.ewma_influences) else None,
        )
        ares = dalerts.eval_rules(
            state.ewma_counters[i],
            cfg.ewma_rules[i],
            avg, p75, tpm,
            eres.signal[:, 0], eres.signal[:, 1],
            params.hard_max_ms, params.suppressed,
        )
        ewma_emissions.append(
            LagEmission(
                eres.window_avg, eres.lower_bound, eres.upper_bound, eres.signal,
                ares.trigger, ares.cause_bits,
            )
        )
        new_estates.append(estate)
        new_ecounters.append(ares.counters)

    emission = TickEmission(
        tpm, new_values, res.count, res.overflowed,
        tuple(lag_emissions), tuple(ewma_emissions),
    )
    new_state = EngineState(
        stats_state, tuple(new_zstates), tuple(new_counters),
        tuple(new_estates), tuple(new_ecounters),
    )
    return emission, new_state, tuple(pushes)


def engine_tick(
    state: EngineState, cfg: EngineConfig, new_label, params: EngineParams
) -> Tuple[TickEmission, EngineState]:
    """The fused per-interval step — the flagship jittable function
    (single-program form; latency-critical hosts use make_engine_step)."""
    emission, new_state, _ = _engine_tick_impl(state, cfg, new_label, params, None)
    return emission, new_state


def engine_core_tick(
    state: EngineState, cfg: EngineConfig, new_label, params: EngineParams,
    evicted: Tuple[jnp.ndarray, ...],
) -> Tuple[TickEmission, EngineState, Tuple[jnp.ndarray, ...]]:
    """Ring-free fused tick (staged mode; see _engine_tick_impl)."""
    return _engine_tick_impl(state, cfg, new_label, params, evicted)


def engine_core_tick_stats(
    state: EngineState, cfg: EngineConfig, new_label, params: EngineParams,
    evicted: Tuple[jnp.ndarray, ...], stats_res: dstats.TickResult,
) -> Tuple[TickEmission, EngineState, Tuple[jnp.ndarray, ...]]:
    """Ring-free fused tick over HOST-completed window stats (the
    native-percentile staging; see _engine_tick_impl)."""
    return _engine_tick_impl(state, cfg, new_label, params, evicted, stats_res)


def fused_copy_bytes(cfg: EngineConfig) -> int:
    """Bytes of big state a FUSED program may rewrite/copy per tick on
    XLA:CPU (the sample reservoir plus every z-score ring — a single program
    that both reads and writes a donated buffer pays a whole-buffer copy
    there). The auto executor gate compares this against the fused budget:
    below it the saved dispatches dwarf the copies, above it the staged
    read-free-writer choreography is mandatory."""
    st = cfg.stats
    dt_bytes = jnp.dtype(st.dtype).itemsize
    total = st.capacity * st.num_buckets * st.samples_per_bucket * dt_bytes
    for spec in cfg.lags:
        zc = zscore_cfg(cfg, spec)
        total += cfg.capacity * 3 * spec.lag * jnp.dtype(zc.storage_dtype).itemsize
    return total


# auto-gate budget: measured on the one-core CPU fallback, the fused
# executor wins up to ~tens of MB of copy-prone state (the rolling/replay
# shapes are ~2 MB; the 8192 x 8640 headline shape is ~850 MB and must stay
# staged). Overridable for experiments via APM_FUSED_MAX_BYTES.
_FUSED_MAX_BYTES_DEFAULT = 32 * 1024 * 1024


def resolve_tick_executor(cfg: EngineConfig) -> str:
    """The ONE executor-choice rule ("fused" | "staged"), shared by the
    single-chip and pod builders so hosts of a pod cannot diverge on it
    (the choice changes the dispatch sequence; divergence would deadlock
    pod collectives — parallel/sharded.py folds this into its pod-global
    agreement alongside the native-percentile capability flag)."""
    mode = os.environ.get("APM_TICK_EXECUTOR") or cfg.tick_executor
    if mode not in ("auto", "fused", "staged"):
        raise ValueError(f"tick executor must be auto|fused|staged, got {mode!r}")
    if mode != "auto":
        return mode
    budget = int(os.environ.get("APM_FUSED_MAX_BYTES", _FUSED_MAX_BYTES_DEFAULT))
    return "fused" if fused_copy_bytes(cfg) <= budget else "staged"


def _use_native_percentiles(cfg: EngineConfig) -> bool:
    """The native-percentile-stage gate shared by the staged and fused
    executors (CPU backend, f32, toolchain present): the host nth_element/
    radix kernel replaces XLA's one-core top_k (~3x, and far more at dense
    windows). On TPU the in-program top_k is the right shape instead."""
    if (
        cfg.stats.percentile_impl in ("auto", "native")
        and cfg.stats.dtype != jnp.float64
        and jax.default_backend() == "cpu"
    ):
        from . import native as _native

        return _native.have_native_percentiles()
    return False


def _rebuild_rotation(cfg: EngineConfig):
    """(chunk, starts) of the staggered-rebuild rotation — the same clamped
    schedule RebuildScheduler walks, for executors that fold the rebuild
    chunk into the tick program."""
    S = cfg.capacity
    chunk = dzscore.rebuild_chunk_rows(S, cfg.zscore_rebuild_every)
    n_chunks = -(-S // chunk)
    return chunk, [min(i * chunk, S - chunk) for i in range(n_chunks)]


def _staged_ring_update(cfg: EngineConfig, state2: EngineState, pushes):
    """Apply this tick's ring pushes to ``state2`` (slot = cursor - 1, the
    pre-advance cursor) — the in-program form of the staged write program,
    shared by the fused executors and make_megatick."""
    sliding_idx = sliding_lag_indices(cfg)
    zs = list(state2.zscores)
    for i, push in zip(sliding_idx, pushes):
        z = zs[i]
        L = z.values.shape[-1]
        zs[i] = z._replace(values=dzscore.ring_write(z.values, push, (z.pos - 1) % L))
    return state2._replace(zscores=tuple(zs))


def make_fused_step(cfg: EngineConfig, *, integrate_rebuild: bool = True):
    """The FUSED per-tick executor: ``step(state, new_label, params) ->
    (emission, new_state)`` in ONE donated dispatch (or two around the host
    percentile kernel) instead of the staged path's five-plus.

    This is the dispatch-floor fix for small shapes (VERDICT r5 weak 2): at
    the reference's real scale (~100 services, ~1,200 metrics/tick) the
    staged executor's per-tick cost is dominated by fixed overhead — five
    program dispatches, the latest-label host sync, and per-stage
    device_puts — worth ~2 ms against ~0.3 ms of actual compute. Here the
    whole tick (label advance -> staggered-rebuild chunk -> window stats ->
    quantize -> z-score -> alerts -> ring writes) is one jitted program over
    the donated EngineState, with the new label a TRACED scalar
    (ops/stats.py advance_span absorbs any label jump in-program, so there
    is no host mirror and no device->host sync).

    Two forms, picked by the same native-percentile gate as the staged
    executor:
      - native (CPU + toolchain): TWO programs — A = advance + z-ring evict
        reads + window panel stats + the staggered-rebuild chunk (the ring
        is only ever READ here, so no copy at any shape); the host fills
        exact percentiles straight from the (zero-copy) sample reservoir
        via the native selection kernel; B = the ring-free core + in-place
        ring writes (the ring's ONLY use in B is the DUS operand). A bucket
        overflow falls back to the count-weighted jitted percentiles for
        that tick, exactly like the staged path.
      - fused-all (TPU / no toolchain / f64): everything including the
        in-program percentiles in ONE program.

    The staggered rebuild rides the tick program on a rotating chunk (same
    schedule as RebuildScheduler; ``step.rebuild_integrated`` tells the host
    loop to skip its separate scheduler). It runs at the START of the tick —
    rebuild-then-tick, where the staged host loop runs tick-then-rebuild —
    because the chunk pass must only ever READ the ring: reading any slice
    of a ring the same program DUS-writes forces a whole-ring copy on
    XLA:CPU (measured 736 ms at [8192, 3, 8640]). Every row is still
    exactly re-aggregated once per ``zscore_rebuild_every`` ticks — the
    drift/blind-spot bound is phase-shifted by one tick, not weakened.

    Unlike the staged executor the rebuild chunk here is XLA, not the
    native streaming kernel — at the small shapes the fused path targets,
    the [chunk, 3, L] slice reduce is microseconds; at shapes where the
    native kernel's ~25x matters, resolve_tick_executor picks staged
    anyway."""
    sliding_idx = sliding_lag_indices(cfg)
    rebuild = integrate_rebuild and engine_needs_rebuild(cfg)
    if rebuild:
        chunk, starts = _rebuild_rotation(cfg)
    else:
        chunk, starts = 0, [0]
    rot = {"i": 0}

    def _next_start():
        s = starts[rot["i"]]
        rot["i"] = (rot["i"] + 1) % len(starts)
        return np.int32(s)

    use_native = _use_native_percentiles(cfg)

    if not use_native:

        def fused_all(state, nl, params, rb_start):
            state = state._replace(stats=dstats.advance_span(state.stats, cfg.stats, nl))
            if rebuild:
                state = engine_rebuild_slice(state, cfg, rb_start, chunk)
            rings = tuple(state.zscores[i].values for i in sliding_idx)
            cursors = tuple(state.zscores[i].pos for i in sliding_idx)
            evicted = tuple(
                dzscore.ring_evict_read(r, g) for r, g in zip(rings, cursors)
            )
            emission, state2, pushes = engine_core_tick(state, cfg, nl, params, evicted)
            return emission, _staged_ring_update(cfg, state2, pushes)

        jfused = jax.jit(fused_all, donate_argnums=(0,))

        def step(state, new_label, params):
            # np scalars: a jnp.int32() here would dispatch a device
            # convert per tick before the program even launches
            return jfused(state, np.int32(new_label), params, _next_start())

        step.rebuild_integrated = rebuild
        step.kind = "fused"
        step.rebuild_rot = rot
        step.rebuild_chunk = chunk
        step.rebuild_starts = starts
        return step

    # ---- native-percentile form: two programs around the host kernel ----
    from .native import window_percentiles_native

    def pre_program(stats_state, aggs, rings, cursors, fills, nl, rb_start):
        st = dstats.advance_span(stats_state, cfg.stats, nl)
        evicted = tuple(
            dzscore.ring_evict_read(r, g) for r, g in zip(rings, cursors)
        )
        res = dstats.window_pre(st, cfg.stats)
        if rebuild:
            new_aggs = []
            for k, i in enumerate(sliding_idx):
                zc = zscore_cfg(cfg, cfg.lags[i])
                zstate = dzscore.ZScoreState(rings[k], fills[k], cursors[k], aggs[k])
                zstate = dzscore.rebuild_agg_slice(zstate, zc, rb_start, chunk)
                new_aggs.append(zstate.agg)
            aggs = tuple(new_aggs)
        # the host needs the overflow decision and the window-slot mask;
        # producing both IN-PROGRAM keeps the host free of blocking scalar
        # reads (int(latest_bucket) costs a per-tick sync on the dispatch
        # queue) — the zero-copy views of these outputs carry the wait
        nbk = cfg.stats.num_buckets
        off = jnp.arange(cfg.stats.buffer_sz, cfg.stats.num_keep + 1, dtype=jnp.int32)
        in_window = jnp.zeros((nbk,), bool).at[(st.latest_bucket - off) % nbk].set(True)
        return st, evicted, res, aggs, jnp.any(res.overflowed), in_window

    # donate the stats state and the [S, 3] aggregates; the rings are READ
    # ONLY here (donating them would free the buffers program B writes)
    jpre = jax.jit(pre_program, donate_argnums=(0, 1))

    def core_pct(state, nl, params, evicted, res, pct):
        # splice the host-selected percentiles in-program: one [S, 2] put
        # instead of two separate device arrays
        res = res._replace(per75=pct[:, 0], per95=pct[:, 1])
        emission, state2, pushes = engine_core_tick_stats(
            state, cfg, nl, params, evicted, res
        )
        return emission, _staged_ring_update(cfg, state2, pushes)

    def core_res(state, nl, params, evicted, res):
        emission, state2, pushes = engine_core_tick_stats(
            state, cfg, nl, params, evicted, res
        )
        return emission, _staged_ring_update(cfg, state2, pushes)

    jcore_pct = jax.jit(core_pct, donate_argnums=(0,))
    jcore_res = jax.jit(core_res, donate_argnums=(0,))
    weighted = jax.jit(dstats.window_stats, static_argnums=1)
    weighted_cfg = cfg.stats._replace(percentile_impl="sort")

    # apm: sync-boundary: the fused executor's single sanctioned readiness wait — dlpack views of program A's outputs feed the host percentile kernel between the two donated programs
    def step(state, new_label, params):
        nl = np.int32(new_label)
        aggs = tuple(state.zscores[i].agg for i in sliding_idx)
        rings = tuple(state.zscores[i].values for i in sliding_idx)
        cursors = tuple(state.zscores[i].pos for i in sliding_idx)
        fills = tuple(state.zscores[i].fill for i in sliding_idx)
        st, evicted, res, new_aggs, overflowed, in_window = jpre(
            state.stats, aggs, rings, cursors, fills, nl, _next_start()
        )
        zs = list(state.zscores)
        for i, agg in zip(sliding_idx, new_aggs):
            zs[i] = zs[i]._replace(agg=agg)
        state = state._replace(stats=st, zscores=tuple(zs))
        # one readiness wait covers everything below: the zero-copy views of
        # A's outputs block until A lands; the overflow flag and the window
        # mask (anchored at the POST-advance latest, stale ticks clamped)
        # ride the same views instead of per-tick jax-scalar fetches
        try:
            overflow_np = np.from_dlpack(overflowed)
            mask = np.from_dlpack(in_window)
            samples = np.from_dlpack(st.samples)  # zero-copy on CPU
            counts = np.from_dlpack(st.nsamples)
        except Exception:  # pragma: no cover - dlpack unavailable
            overflow_np = np.asarray(overflowed)
            mask = np.asarray(in_window)
            samples = np.asarray(st.samples)
            counts = np.asarray(st.nsamples)
        if bool(overflow_np):
            # reservoir overflow: the count-weighted jitted path keeps burst
            # arrival mass exact for this tick (same fallback as staged)
            return jcore_res(state, nl, params, evicted, weighted(st, weighted_cfg))
        pct = window_percentiles_native(samples, mask, (75, 95), counts)
        return jcore_pct(state, nl, params, evicted, res, pct)

    step.rebuild_integrated = rebuild
    step.kind = "fused-native"
    step.rebuild_rot = rot
    step.rebuild_chunk = chunk
    step.rebuild_starts = starts
    return step


def make_megatick(cfg: EngineConfig, n_slots: int, batch_per_slot: int):
    """The MEGATICK executor: K buffered (tick?, ingest) slots in ONE
    donated ``lax.scan`` dispatch — replay/catch-up amortization for shapes
    where per-tick dispatch overhead dominates and a K-tick emission delay
    is acceptable (detection latency trades at K x 10 s of LOG time, which
    replay compresses to milliseconds of wall time).

    ``mega(state, params, new_labels[K], do_ticks[K], rows[K,B], labels[K,B],
    elapsed[K,B], valid[K,B]) -> (stacked TickEmission, new_state)``. Each
    slot optionally ticks FIRST (the stats-before-addData event order:
    entries that crossed a boundary are ingested after the tick they
    triggered), then scatters its micro-batch; slots with ``do_tick`` False
    are ingest-only (their emission slot is NaN/zero filler — mask by do_tick).
    The staggered-rebuild chunk rides every ticking slot, same rotation as
    make_fused_step (the wrapper threads the rotation across calls).

    Percentiles run IN-PROGRAM (the host selection kernel cannot ride a
    scan), so on the one-core CPU fallback this path loses to the fused
    native executor at dense windows — it is the TPU-shape amortizer, kept
    honest by the dispatch-floor microbench measuring both."""
    sliding_idx = sliding_lag_indices(cfg)
    rebuild = engine_needs_rebuild(cfg)
    chunk, starts = _rebuild_rotation(cfg) if rebuild else (0, [0])
    rot = {"i": 0}

    def tick_body(state, nl, rb_start, params):
        state = state._replace(stats=dstats.advance_span(state.stats, cfg.stats, nl))
        if rebuild:
            state = engine_rebuild_slice(state, cfg, rb_start, chunk)
        rings = tuple(state.zscores[i].values for i in sliding_idx)
        cursors = tuple(state.zscores[i].pos for i in sliding_idx)
        evicted = tuple(dzscore.ring_evict_read(r, g) for r, g in zip(rings, cursors))
        emission, state2, pushes = engine_core_tick(state, cfg, nl, params, evicted)
        return emission, _staged_ring_update(cfg, state2, pushes)

    def mega(state, params, nls, do_ticks, rb_starts, rows, labels, elaps, valid):
        # the no-tick branch must match the tick branch's exact leaf dtypes
        # (x64 mode weak-promotes tpm/count); derive them abstractly
        em_struct = jax.eval_shape(
            lambda s: tick_body(s, nls[0], rb_starts[0], params)[0], state
        )
        zero_em = jax.tree.map(
            lambda l: jnp.full(l.shape, jnp.nan, l.dtype)
            if jnp.issubdtype(l.dtype, jnp.floating)
            else jnp.zeros(l.shape, l.dtype),
            em_struct,
        )

        def slot(st, xs):
            nl, do_tick, rb_start, r, l, e, v = xs
            emission, st = jax.lax.cond(
                do_tick,
                lambda s: tick_body(s, nl, rb_start, params),
                lambda s: (zero_em, s),
                st,
            )
            st = engine_ingest(st, cfg, r, l, e, v)
            return st, emission

        state, emissions = jax.lax.scan(
            slot, state, (nls, do_ticks, rb_starts, rows, labels, elaps, valid)
        )
        return emissions, state

    jmega = jax.jit(mega, donate_argnums=(0,))

    def step(state, params, new_labels, do_ticks, rows, labels, elaps, valid):
        K = len(new_labels)
        if K != n_slots or rows.shape != (n_slots, batch_per_slot):
            raise ValueError(
                f"megatick compiled for [{n_slots}, {batch_per_slot}] slots, "
                f"got {K} labels / batch {rows.shape}"
            )
        rb = np.zeros(K, np.int32)
        for j, dt_ in enumerate(np.asarray(do_ticks, bool)):
            if dt_ and rebuild:
                rb[j] = starts[rot["i"]]
                rot["i"] = (rot["i"] + 1) % len(starts)
        return jmega(
            state, params,
            jnp.asarray(new_labels, jnp.int32), jnp.asarray(do_ticks, bool),
            jnp.asarray(rb), jnp.asarray(rows, jnp.int32),
            jnp.asarray(labels, jnp.int32),
            jnp.asarray(elaps, cfg.stats.dtype), jnp.asarray(valid, bool),
        )

    step.rebuild_integrated = rebuild
    step.kind = "megatick"
    step.rebuild_rot = rot
    step.rebuild_chunk = chunk
    step.rebuild_starts = starts
    return step


def make_engine_step(cfg: EngineConfig):
    """The staged per-tick executor: ``step(state, new_label, params) ->
    (emission, new_state)`` with donation throughout.

    Up to four program kinds per tick, each touching the big buffers only
    in the way XLA can keep in place:
      1. stats advance: dstats.advance_one per new label (host-counted from
         the latest-label scalar; normally one call) — the sample-reservoir
         clear is a single dynamic_update_slice, never a whole-buffer
         select,
      2. evict-read: one program slicing every sliding ring's about-to-be-
         overwritten slot (read-only — the rings must NOT be donated here),
      3. core tick: everything else — window_stats and the sliding lags
         only READ the big buffers, which pass through as donated identity,
      4. ring-write: one program of pure dynamic_update_slices (donated —
         the ONLY writer of the z-score rings; any same-program read would
         force a whole-ring copy on XLA:CPU, measured 736 ms vs 0.6 ms at
         [8192, 3, 8640]).

    On the CPU backend (percentileImpl auto/native, f32, toolchain present)
    the percentile stage additionally moves to the HOST: a tiny jitted
    program computes the panel stats, the native nth_element kernel selects
    the exact reference percentiles straight from the (zero-copy) sample
    reservoir, and the core program receives the completed TickResult —
    ~3x cheaper than one-core XLA top_k. Any bucket overflow falls back to
    the jitted count-weighted path for that tick. On TPU the in-program
    top_k is the right shape and this stage stays fused.

    Executor selection (resolve_tick_executor): small shapes route to the
    FUSED executor (make_fused_step — the dispatch-floor fix), big shapes
    keep the staging described above; ``tpuEngine.tickExecutor`` /
    APM_TICK_EXECUTOR pin either explicitly."""
    if resolve_tick_executor(cfg) == "fused":
        return make_fused_step(cfg)
    use_native = _use_native_percentiles(cfg)

    if not use_native:
        core = jax.jit(engine_core_tick, static_argnums=1, donate_argnums=(0,))
        return make_staged_executor(
            cfg,
            core=lambda state, nl, params, evicted: core(state, cfg, nl, params, evicted),
        )

    from .native import window_percentiles_native

    pre = jax.jit(dstats.window_pre, static_argnums=1)
    # overflow tick: the count-weighted sort keeps burst arrival mass exact
    weighted = jax.jit(
        dstats.window_stats, static_argnums=1
    )
    weighted_cfg = cfg.stats._replace(percentile_impl="sort")
    core = jax.jit(engine_core_tick_stats, static_argnums=1, donate_argnums=(0,))
    NB = cfg.stats.num_buckets
    offsets = np.arange(cfg.stats.buffer_sz, cfg.stats.num_keep + 1)

    # apm: sync-boundary: staged executor's host percentile stage — the overflow probe and reservoir readback sit between the pre and core programs by design
    def native_core(state, nl, params, evicted):
        res = pre(state.stats, cfg.stats)
        if bool(np.asarray(res.overflowed).any()):
            res = weighted(state.stats, weighted_cfg)
        else:
            # anchor the window at the POST-advance latest label, exactly
            # like window_pre/window_stats — on a stale re-emission tick
            # (nl < latest: restore/replay out-of-order delivery) the
            # advance loop left latest unchanged and nl would select the
            # wrong slots
            latest = int(state.stats.latest_bucket)
            mask = np.zeros(NB, bool)
            mask[(latest - offsets) % NB] = True
            try:
                samples = np.from_dlpack(state.stats.samples)  # zero-copy on CPU
                counts = np.from_dlpack(state.stats.nsamples)
            except Exception:  # pragma: no cover - dlpack unavailable
                samples = np.asarray(state.stats.samples)
                counts = np.asarray(state.stats.nsamples)
            # counts = the filled-prefix panel: the kernel gathers only live
            # samples instead of NaN-scanning every CAP slot (stats.ingest
            # fills positions in order; reservoir replacement stays inside
            # the prefix, so validity == prefix membership)
            pct = window_percentiles_native(samples, mask, (75, 95), counts)
            res = res._replace(
                per75=jnp.asarray(pct[:, 0], cfg.stats.dtype),
                per95=jnp.asarray(pct[:, 1], cfg.stats.dtype),
            )
        return core(state, cfg, nl, params, evicted, res)

    return make_staged_executor(cfg, core=native_core)


def sliding_lag_indices(cfg: EngineConfig) -> Tuple[int, ...]:
    """Which lags maintain sliding aggregates (ring staging applies)."""
    return tuple(
        i for i, spec in enumerate(cfg.lags) if zscore_cfg(cfg, spec).sliding_active
    )


def staged_ring_programs():
    """The two ring-only jitted programs of the staging contract, shared by
    the single-chip and pod executors: the read-only evict slices and the
    donated pure-DUS writes (write slot = the cursor BEFORE the core
    advanced it = new_pos - 1)."""
    evict = jax.jit(
        lambda rings, cursors: tuple(
            dzscore.ring_evict_read(r, g) for r, g in zip(rings, cursors)
        )
    )
    write = jax.jit(
        lambda rings, pushes, new_cursors: tuple(
            dzscore.ring_write(r, p, (g - 1) % r.shape[-1])
            for r, p, g in zip(rings, pushes, new_cursors)
        ),
        donate_argnums=(0,),
    )
    return evict, write


def make_staged_executor(cfg: EngineConfig, *, core):
    """The ONE staging choreography (single-chip make_engine_step and the
    pod-scale parallel.sharded.make_sharded_step both run on it, so the
    label-advance clamp, evict/write slot math and donation ordering cannot
    drift between them).

    ``core(state, new_label_int, params, evicted) -> (*outs, new_state,
    pushes)`` is the ring-free fused program (possibly shard_mapped, possibly
    emitting extra outputs like the fleet rollup); the returned
    ``step(state, new_label, params) -> (*outs, new_state)`` wraps it with:

      1. stats ring advance, one label at a time (a jump clears at most NB
         slots — the ring only holds NB labels). The latest-label scalar is
         already host-visible from the previous step; reading it keeps the
         host counter self-healing across restores.
      2. the read-only z-ring evict slices,
      3. the core program,
      4. the in-place pure-DUS ring writes.
    """
    sliding_idx = sliding_lag_indices(cfg)
    NB = cfg.stats.num_buckets
    advance = jax.jit(dstats.advance_one, static_argnums=1, donate_argnums=(0,))
    evict, write = staged_ring_programs()

    def step(state, new_label, params):
        latest = int(state.stats.latest_bucket)
        nl = int(new_label)
        st = state.stats
        for lbl in range(max(latest + 1, nl - NB + 1), nl + 1):
            st = advance(st, cfg.stats, lbl)
        state = state._replace(stats=st)

        rings = tuple(state.zscores[i].values for i in sliding_idx)
        cursors = tuple(state.zscores[i].pos for i in sliding_idx)
        evicted = evict(rings, cursors) if sliding_idx else ()
        *outs, state2, pushes = core(state, nl, params, evicted)
        if sliding_idx:
            rings2 = tuple(state2.zscores[i].values for i in sliding_idx)
            new_cursors = tuple(state2.zscores[i].pos for i in sliding_idx)
            new_rings = write(rings2, pushes, new_cursors)
            zs = list(state2.zscores)
            for i, ring in zip(sliding_idx, new_rings):
                zs[i] = zs[i]._replace(values=ring)
            state2 = state2._replace(zscores=tuple(zs))
        return (*outs, state2)

    step.rebuild_integrated = False
    step.kind = "staged"
    return step


def engine_ingest(state: EngineState, cfg: EngineConfig, rows, labels, elapsed, valid) -> EngineState:
    return state._replace(
        stats=dstats.ingest(state.stats, cfg.stats, rows, labels, elapsed, valid)
    )


def engine_rebuild_aggs(state: EngineState, cfg: EngineConfig) -> EngineState:
    """Amortized exact rebuild of every sliding lag's running aggregates.

    Host loops (PipelineDriver, bench) call this every
    ``cfg.zscore_rebuild_every`` ticks; jittable and donation-friendly. A
    no-op (identity pytree) when no lag runs sliding."""
    zstates = tuple(
        dzscore.rebuild_agg_state(z, zscore_cfg(cfg, spec))
        for z, spec in zip(state.zscores, cfg.lags)
    )
    return state._replace(zscores=zstates)


def engine_needs_rebuild(cfg: EngineConfig) -> bool:
    """True when any lag maintains sliding aggregates (the host loop then
    owes a periodic engine_rebuild_aggs call)."""
    return any(zscore_cfg(cfg, spec).sliding_active for spec in cfg.lags)


def engine_rebuild_slice(state: EngineState, cfg: EngineConfig, row_start, chunk: int) -> EngineState:
    """One STAGGERED-rebuild step: exact re-aggregation of ring rows
    [row_start, row_start+chunk) for every sliding lag (ops/zscore.py
    rebuild_agg_slice). RebuildScheduler calls this every tick on a rotating
    chunk so the whole ring is re-aggregated once per
    ``cfg.zscore_rebuild_every`` ticks with no tick ever paying a full ring
    pass — the production cadence replacing the monolithic
    engine_rebuild_aggs stall. jittable; ``cfg``/``chunk`` static."""
    zstates = tuple(
        dzscore.rebuild_agg_slice(z, zscore_cfg(cfg, spec), row_start, chunk)
        for z, spec in zip(state.zscores, cfg.lags)
    )
    return state._replace(zscores=zstates)


def cpu_zero_copy_view(arr) -> np.ndarray:
    """Zero-copy numpy view of a CPU-backend device array (or one
    addressable shard's block). bfloat16 buffers — which numpy's dlpack
    import rejects — are exposed as their raw uint16 bit pattern straight
    from the device buffer (native/rebuild.cpp's is_bf16 branch decodes
    bits << 16), so no full-size cast ever materializes."""
    try:
        return np.from_dlpack(arr)
    except Exception:
        if arr.dtype.itemsize != 2:
            # the bit-view fallback is ONLY for 2-byte (bf16) buffers; a
            # wider dtype failing dlpack must surface, not decode as garbage
            raise
        if len(arr.addressable_shards) != 1:
            # shard 0's buffer holds only a fraction of a multi-shard
            # array's elements — reshaping it to the full shape would be an
            # out-of-bounds read; callers view per-shard blocks instead
            raise ValueError(
                "cpu_zero_copy_view bit-view fallback requires a "
                f"single-shard array, got {len(arr.addressable_shards)} shards"
            )
        import ctypes

        n = int(np.prod(arr.shape))
        ptr = arr.addressable_shards[0].data.unsafe_buffer_pointer()
        buf = (ctypes.c_uint16 * n).from_address(ptr)
        return np.frombuffer(buf, np.uint16).reshape(arr.shape)


def default_native_rebuild_gate(cfg: EngineConfig) -> bool:
    """ONE definition of "may the staggered rebuild use the native streaming
    kernel" shared by the single-chip and pod schedulers: CPU backend,
    single process, f32 compute, and a ring storage dtype the kernel
    decodes (f32 bits or bf16 bits)."""
    return (
        jax.default_backend() == "cpu"
        and jax.process_count() == 1
        and cfg.stats.dtype != jnp.float64
        and cfg.zscore_ring_dtype in (None, jnp.float32, jnp.bfloat16)
    )


class _StaggeredRebuildBase:
    """Shared shell of the two staggered-rebuild schedulers: the chunk
    rotation, the native-try/permanent-fallback policy, and the benchmark
    sync boundary. Subclasses provide ``_native_step(state, start)`` and
    ``_slice_call(state, start)`` plus all their construction."""

    active: bool = False

    def step_synced(self, state: EngineState) -> EngineState:
        """step() + block until the merged aggregates are materialized — the
        timing boundary benchmarks charge (one definition of "what must be
        waited on", instead of copies reaching into _sliding_idx)."""
        state = self.step(state)
        if self.active:
            jax.block_until_ready([state.zscores[i].agg for i in self._sliding_idx])
        return state

    def step(self, state: EngineState) -> EngineState:
        """Rebuild this tick's due chunk; returns the updated state."""
        if not self.active:
            return state
        start = self.starts[self._i]
        self._i = (self._i + 1) % self.n_chunks
        if self._native:
            try:
                return self._native_step(state, start)
            except Exception:
                # e.g. dlpack view unavailable — fall back permanently, but
                # never silently: the jitted slice path is ~25x slower on CPU
                self._native = False
                import logging

                logging.getLogger(type(self).__module__).warning(
                    "native staggered rebuild failed; falling back to the "
                    "jitted slice path for the rest of the process",
                    exc_info=True,
                )
        return self._slice_call(state, start)


class RebuildScheduler(_StaggeredRebuildBase):
    """Host-side rotation of the staggered sliding-aggregate rebuild.

    ``step(state)`` is called once per engine tick; it rebuilds ONE
    contiguous row chunk (rebuild_chunk_rows sizes it so a full rotation
    spans ``cfg.zscore_rebuild_every`` ticks) and returns the new state.
    Every row's rebuild interval stays <= rebuild_every ticks — the same
    drift/blind-spot bound as the monolithic pass, minus the multi-second
    tick stall at pod shapes (the reference pays its window recompute on
    EVERY entry, stream_calc_z_score.js:66-104; this is the amortized
    replacement being staggered).

    Backend-adaptive like the percentile stage: on the single-process CPU
    backend with the toolchain present, the chunk pass runs in the native
    streaming kernel (native/rebuild.cpp, ~25x the XLA:CPU variadic reduce)
    against zero-copy dlpack ring views, and only the [chunk, 3] partials
    enter the jitted merge (ops/zscore.py merge_agg_slice). Everywhere else
    (TPU, no toolchain) the whole slice rebuild runs in one jitted program
    — on TPU the fused reduce over a [chunk, 3, L] slice is microseconds.
    A native-path failure permanently falls back to the jitted path.
    """

    def __init__(self, cfg: EngineConfig, *, allow_native: Optional[bool] = None):
        self.cfg = cfg
        self.active = engine_needs_rebuild(cfg)
        if not self.active:
            return
        S = cfg.capacity
        self.chunk = dzscore.rebuild_chunk_rows(S, cfg.zscore_rebuild_every)
        self.n_chunks = -(-S // self.chunk)
        # ragged tail chunks clamp (re-rebuilding a few rows is harmless —
        # the rebuild is idempotent) so ONE compiled program serves all
        self.starts = [min(i * self.chunk, S - self.chunk) for i in range(self.n_chunks)]
        self._i = 0
        self._sliding_idx = sliding_lag_indices(cfg)
        self._slice_fn = jax.jit(
            engine_rebuild_slice, static_argnums=(1, 3), donate_argnums=(0,)
        )
        if allow_native is None:
            allow_native = default_native_rebuild_gate(cfg)
        self._native = False
        if allow_native:
            from . import native as _native

            self._native = _native.have_native_rebuild()
        if self._native:

            def _make_merge(zc):
                def m(agg, row_start, cnt, vsum, vsumsq, anchor, vmin, vmax, last_push):
                    return dzscore.merge_agg_slice(
                        agg, zc, row_start, cnt, vsum, vsumsq, anchor, vmin, vmax, last_push
                    )

                # NO donation: the [S, 3] leaf copies are noise, and keeping
                # the old agg buffers alive makes the jitted fallback safe
                # even if a multi-lag native step fails halfway through
                return jax.jit(m)

            self._merge_fns = {
                i: _make_merge(zscore_cfg(cfg, cfg.lags[i])) for i in self._sliding_idx
            }

    def _slice_call(self, state: EngineState, start: int) -> EngineState:
        return self._slice_fn(state, self.cfg, start, self.chunk)

    # apm: sync-boundary: rebuild scheduler's native window-agg pass reads the ring chunk back for the C++ kernel (merge returns to device)
    def _native_step(self, state: EngineState, start: int) -> EngineState:
        from . import native as _native

        zs = list(state.zscores)
        end = start + self.chunk
        for i in self._sliding_idx:
            z = zs[i]
            agg = z.agg
            ring = cpu_zero_copy_view(z.values)  # zero-copy on the CPU backend
            cnt = np.from_dlpack(agg.cnt)[start:end]
            vsum = np.from_dlpack(agg.vsum)[start:end]
            anc = np.from_dlpack(agg.anchor)[start:end]
            # the incremental mean as the variance anchor (rebuild_agg_state);
            # maximum(cnt,1) values are exact in f32, so this matches the
            # jitted producer's f32 arithmetic
            anchor_est = np.where(
                cnt > 0, anc + vsum / np.maximum(cnt, 1).astype(np.float32), anc
            ).astype(np.float32)
            L = ring.shape[-1]
            last_slot = (int(z.pos) - 1) % L
            c, vs, vs2, mn, mx, lastp = _native.window_aggs_native(
                ring[start:end], anchor_est, last_slot
            )
            zs[i] = z._replace(
                agg=self._merge_fns[i](agg, start, c, vs, vs2, anchor_est, mn, mx, lastp)
            )
        return state._replace(zscores=tuple(zs))


def engine_derive_aggs(state: EngineState, cfg: EngineConfig) -> EngineState:
    """Derive the sliding aggregates from freshly-restored rings — the ONE
    restore-time derivation, shared by the npz load_resume and the orbax
    checkpoint restore (the aggregates are never serialized; SlidingAgg
    docstring)."""
    zstates = []
    for z, spec in zip(state.zscores, cfg.lags):
        zc = zscore_cfg(cfg, spec)
        agg = dzscore.build_agg(z.values, zc, z.pos) if zc.sliding_active else None
        zstates.append(z._replace(agg=agg))
    return state._replace(zscores=tuple(zstates))


def build_engine_config(apm_config: dict, capacity: Optional[int] = None) -> EngineConfig:
    """Derive EngineConfig from the APM config tree (apm_config.json shape)."""
    eng = apm_config.get("tpuEngine", {})
    calc = apm_config.get("streamCalcStats", {})
    zcfg = apm_config.get("streamCalcZScore", {})
    acfg = apm_config.get("streamProcessAlerts", {})

    if capacity is None:
        capacity = int(eng.get("serviceCapacity", 1024))
    dtype = jnp.float64 if eng.get("dtype") == "float64" else jnp.float32
    ring_name = eng.get("zscoreRingDtype") or None
    if ring_name is not None:
        ring_dtypes = {"float32": jnp.float32, "float64": jnp.float64,
                       "bfloat16": jnp.bfloat16}
        if ring_name not in ring_dtypes:
            raise ValueError(
                f"tpuEngine.zscoreRingDtype must be one of {sorted(ring_dtypes)}, "
                f"got {ring_name!r}"
            )
        ring_dtype = ring_dtypes[ring_name]
        if ring_dtype == dtype:
            ring_dtype = None  # same as compute dtype: keep configs hashable-equal
    else:
        ring_dtype = None
    stats_cfg = dstats.StatsConfig(
        capacity=capacity,
        window_sz=int(calc.get("windowSizeInIntervals", 30)),
        buffer_sz=int(calc.get("bufferSizeInIntervals", 6)),
        interval_len_s=int(calc.get("intervalLengthInSeconds", 10)),
        samples_per_bucket=int(eng.get("samplesPerBucket", 128)),
        dtype=dtype,
        percentile_impl=str(eng.get("percentileImpl", "auto")),
    )
    suppressed_lags = {int(x) for x in acfg.get("suppressedLags", [])}
    lags = tuple(
        LagSpec(
            int(d["LAG"]),
            int(d["LAG"]) in suppressed_lags,
            bool(d.get("ROBUST", False)),
        )
        for d in zcfg.get("defaults", [])
    )
    def rule_for(suppressed: bool) -> dalerts.AlertRuleConfig:
        return dalerts.AlertRuleConfig(
            hard_min_ms=float(acfg.get("hardMinMsAlertThreshold", 200)),
            hard_min_tpm=float(acfg.get("hardMinTpmAlertThreshold", 1.0)),
            alert_on_both_only=bool(acfg.get("alertOnBothOnly", True)),
            window_sz=int(acfg.get("rollingAlertWindowSizeInIntervals", 60)),
            required_bad=int(acfg.get("requiredNumberBadIntervalsInAlertWindowToTrigger", 45)),
            lag_suppressed=suppressed,
        )

    rules = tuple(rule_for(spec.suppressed) for spec in lags)
    ewma_specs = dewma.specs_from_config(eng)
    ewma_rules = tuple(rule_for(spec.suppressed) for spec in ewma_specs)
    vp = str(eng.get("zscoreVariancePass", "auto"))
    if vp not in ("auto", "sliding", "one", "two"):
        raise ValueError(
            f"tpuEngine.zscoreVariancePass must be auto|sliding|one|two, got {vp!r}"
        )
    # "auto" = sliding O(1) aggregates for f32 production (ops/zscore.py pins
    # f64 parity mode and robust lags to the full-window computation
    # regardless of this flag); "one"/"two" force the ring-pass variants
    sliding = vp in ("auto", "sliding")
    onepass = vp != "two"
    tick_exec = str(eng.get("tickExecutor", "auto"))
    if tick_exec not in ("auto", "fused", "staged"):
        raise ValueError(
            f"tpuEngine.tickExecutor must be auto|fused|staged, got {tick_exec!r}"
        )
    return EngineConfig(
        stats=stats_cfg, lags=lags, alert_rules=rules, quantize=True,
        ewma=ewma_specs, ewma_rules=ewma_rules, zscore_ring_dtype=ring_dtype,
        zscore_onepass=onepass, zscore_sliding=sliding,
        zscore_rebuild_every=int(eng.get("zscoreRebuildEvery", 64)),
        tick_executor=tick_exec,
    )


def make_demo_engine(
    capacity: int,
    samples_per_bucket: int,
    lag_settings: Sequence[Tuple[int, float, float]],
    *,
    hard_max_ms: float = 10000.0,
    ewma_channels: Sequence[dict] = (),
    ring_dtype: Optional[str] = None,
) -> Tuple[EngineConfig, EngineState, EngineParams]:
    """(cfg, fresh state, uniform params) for benches/dryruns/tests.

    ``lag_settings`` is [(lag, threshold, influence), ...]; ``ewma_channels``
    is a list of tpuEngine.ewmaChannels dicts (uppercase keys). Single source
    for the engine-setup boilerplate shared by bench.py, __graft_entry__.py
    and the sharding tests.
    """
    from .config import default_config

    cfg_tree = default_config()
    cfg_tree["streamCalcZScore"]["defaults"] = [
        {"LAG": lag, "THRESHOLD": thr, "INFLUENCE": infl}
        for lag, thr, infl in lag_settings
    ]
    cfg_tree["tpuEngine"]["serviceCapacity"] = capacity
    cfg_tree["tpuEngine"]["samplesPerBucket"] = samples_per_bucket
    if ewma_channels:
        cfg_tree["tpuEngine"]["ewmaChannels"] = list(ewma_channels)
    if ring_dtype is not None:
        cfg_tree["tpuEngine"]["zscoreRingDtype"] = ring_dtype
    cfg = build_engine_config(cfg_tree, capacity)
    state = engine_init(cfg)
    S = cfg.capacity
    params = EngineParams(
        thresholds=tuple(
            jnp.full(S, thr, cfg.stats.dtype) for _lag, thr, _infl in lag_settings
        ),
        influences=tuple(
            jnp.full(S, infl, cfg.stats.dtype) for _lag, _thr, infl in lag_settings
        ),
        hard_max_ms=jnp.full(S, hard_max_ms, cfg.stats.dtype),
        suppressed=jnp.zeros(S, bool),
        active=jnp.ones(S, bool),  # demo fleets are fully populated
        # populated whenever channels exist so the params pytree matches the
        # sharded in_specs (parallel/sharded._params_specs mirrors cfg.ewma)
        ewma_thresholds=tuple(
            jnp.full(S, spec.threshold, cfg.stats.dtype) for spec in cfg.ewma
        ),
        ewma_influences=tuple(
            jnp.full(S, spec.influence, cfg.stats.dtype) for spec in cfg.ewma
        ),
    )
    return cfg, state, params


class PipelineDriver:
    """Host loop around the fused device step.

    Consumes TxEntry objects (or raw CSV lines), micro-batches them, splits at
    tick boundaries, and emits:
    - ordered raw tx lines for the DB sink (min-heap drain up to the window
      edge, stream_calc_stats.js:364 role),
    - StatEntry lines ('stats' queue parity),
    - FullStatEntry lines per lag ('z_score' queue parity),
    - AlertEntry via the provided AlertsManager (cooldown applied).
    """

    def __init__(
        self,
        apm_config: dict,
        *,
        capacity: Optional[int] = None,
        alerts_manager=None,
        on_stat: Optional[Callable[[StatEntry], None]] = None,
        on_fullstat: Optional[Callable[[FullStatEntry], None]] = None,
        on_ordered_tx: Optional[Callable[[TxEntry], None]] = None,
        on_ordered_csv: Optional[Callable[[str], None]] = None,
        on_alert: Optional[Callable] = None,
        on_overflow: Optional[Callable[[int, int], None]] = None,
        on_fullstat_csv: Optional[Callable[[List[str]], None]] = None,
        logger=None,
        micro_batch_size: int = 8192,
        async_emission: Optional[bool] = None,
        metrics_registry=None,
    ):
        self.apm_config = apm_config
        self.cfg = build_engine_config(apm_config, capacity)
        self.state = engine_init(self.cfg)
        self.registry = ServiceRegistry(self.cfg.capacity)
        self.alerts_manager = alerts_manager
        self.on_stat = on_stat
        self.on_fullstat = on_fullstat
        if on_ordered_tx is not None and on_ordered_csv is not None:
            raise ValueError(
                "on_ordered_tx and on_ordered_csv are mutually exclusive "
                "(one ordered-tx drain per driver); pick the object heap or "
                "the raw-line fast path"
            )
        self.on_ordered_tx = on_ordered_tx
        # fast-path variant of on_ordered_tx: receives the RAW tx CSV line at
        # the tick-boundary drain, end_ts-ordered, without TxEntry objects or
        # per-entry heap pushes. Served by feed_csv_batch only (feed() keeps
        # the object heap); producers emit normalized to_csv() lines so the
        # raw line is the same wire bytes the object path would re-serialize.
        self.on_ordered_csv = on_ordered_csv
        self._tx_backlog: List[Tuple[float, str]] = []  # (end_ts, raw line)
        self.on_alert = on_alert
        self.on_overflow = on_overflow
        # bulk wire-format emission: receives the tick's FullStat CSV lines
        # for one channel as a list, built without per-row dataclass objects
        # (byte-identical to [fs.to_csv() for fs in ...]); the fast path for
        # queue-writing consumers at 10k-row fleets
        self.on_fullstat_csv = on_fullstat_csv
        self.logger = logger
        # percentile-reservoir overflow telemetry (ops/stats.py reservoir):
        # rows whose window percentile was estimated from a uniform CAP-sample
        # rather than computed exactly — bounded error, but worth alerting on
        # so operators can raise samplesPerBucket if it is chronic
        self.overflow_rows_total = 0
        self.overflow_ticks = 0
        self._overflow_last_logged_tick = -1000
        self.micro_batch_size = micro_batch_size
        # at-least-once delivery coupling (runtime/worker.py epoch cycle):
        # the per-queue {"epoch": n, "dedup": [msg ids], ...} tree the last
        # save_resume carried / load_resume recovered. None = snapshot
        # predates the feature or the worker runs at-most-once.
        self.delivery_state: Optional[dict] = None
        # -- incremental delta-checkpoint capture (deltachain.py) -----------
        # Enabled by enable_delta_capture() (the worker's checkpointMode:
        # "delta"); at-most-once / full-snapshot drivers pay one bool check
        # per bulk feed. Tracking granularity: stats mutations are dirty
        # (row, bucket-slot) CELLS (feeds scatter into exactly those cells;
        # tick ring-advances are derivable from the tick labels), z rings
        # are one pushed column per tick at the shared cursor, EWMA channels
        # one season-slot column per tick — so a delta's size is
        # proportional to the epoch's ingest + tick count, not state size.
        self._delta_track = False
        self._delta_np_gather = False
        self._dirty_cells: set = set()  # packed row*NB+slot ints since last commit
        self._delta_ticks: List[int] = []  # tick labels since last commit
        self._delta_pos0: List[int] = []  # per-lag ring cursor at last commit
        self._delta_reg_base = 0  # registry count at last commit
        self.heap = MinHeap(lambda tx: tx.end_ts)
        self._pending: List[Tuple[int, int, float]] = []  # (row, label, elapsed)
        self._latest_label = 0  # host mirror of stats.latest_bucket (hot path)
        # native batch decoder (native/decoder.cpp): created lazily on the
        # first feed_csv_batch; None = unavailable or disabled, use the
        # numpy path. _decode2row maps decoder key ids -> registry rows.
        self._use_native_decode = bool(
            apm_config.get("tpuEngine", {}).get("nativeDecode", True)
        )
        self._native_dec = None
        self._native_dec_tried = False
        self._reset_decode_map()
        # -- telemetry plane (obs/): per-stage tick tracing + e2e latency ----
        # Host-side perf_counter boundaries ONLY — no new device syncs (the
        # emit stage's np.asarray readback is the blocking sync point we
        # already pay; DESIGN.md §4). Cost is ~5 histogram observes per TICK
        # (microseconds against the ~0.5 ms tick floor); observability.enabled
        # = false removes even that.
        self._telemetry = bool(apm_config.get("observability", {}).get("enabled", True))
        self._intake_oldest_ts: Optional[float] = None  # oldest undelivered ingest stamp
        self._emitting_intake_ts: Optional[float] = None
        # -- distributed trace plane + decision provenance -------------------
        # Sampled per-transaction traces (obs/trace): the worker registers
        # in-flight sampled transactions via note_trace(); the tick that
        # closes a transaction's bucket records its tick/emit/alert spans.
        # Alert decision records (obs/decisions) capture the z inputs behind
        # every page. Both are alert/trace-path only: an unsampled message
        # costs nothing here, and a tick with no live traces pays one
        # truthiness check.
        self._live_traces: deque = deque(maxlen=256)
        self._emitting_traces: Sequence[dict] = ()
        self._emit_wall_start: Optional[float] = None
        # tick wall-clock windows by label: the "tick" span of a claimed
        # trace must describe the tick that CLOSED its bucket even when
        # async-emission delivers the emission one tick late
        self._tick_walls: Dict[int, Tuple[float, float]] = {}
        # host numpy mirrors of the per-row alert params (threshold/influence
        # by channel id) + channel -> device-state index maps; refreshed with
        # the device params, read only on the alert path (decision records)
        self._host_thresholds: Dict = {}
        self._host_influences: Dict = {}
        self._lag_index: Dict = {}
        self._ewma_index: Dict = {}
        if self._telemetry:
            from .obs import get_registry
            from .obs.decisions import get_decisions
            from .obs.registry import DEFAULT_COUNT_BUCKETS
            from .obs.trace import get_tracer
            from .obs.tracing import TickTracer

            self._trace = get_tracer()
            self._decisions = get_decisions()
            reg = metrics_registry if metrics_registry is not None else get_registry()
            self._tracer = TickTracer(reg)
            self._m_capacity = reg.gauge(
                "apm_engine_capacity", "Device state rows allocated [S]"
            )
            self._m_services = reg.gauge(
                "apm_engine_services", "Registered (server, service) rows"
            )
            self._m_tx = reg.counter(
                "apm_engine_tx_ingested_total", "Transactions scattered into device state"
            )
            self._m_overflow_rows = reg.counter(
                "apm_engine_overflow_row_ticks_total",
                "Row-ticks whose percentile fell back to the reservoir estimate",
            )
            self._m_grows = reg.counter(
                "apm_engine_capacity_grows_total", "Capacity-doubling recompiles"
            )
            self._m_emit_lat = reg.histogram(
                "apm_e2e_ingest_to_emit_seconds",
                "Transport ingest stamp -> tick emission fan-out (oldest record)",
            )
            self._m_alert_lat = reg.histogram(
                "apm_e2e_ingest_to_alert_seconds",
                "Transport ingest stamp -> alert dispatch (oldest record)",
            )
            self._m_alerts = reg.counter(
                "apm_alerts_total", "Alert triggers dispatched by the driver"
            )
            self._m_pending_batch = reg.histogram(
                "apm_engine_flush_batch_size",
                "Records per ingest scatter",
                buckets=DEFAULT_COUNT_BUCKETS,
            )
            # wall-clock attribution (obs.attrib): each tick stage the
            # TickTracer splits is a span of its stage clock (busy seconds
            # for the bottleneck estimator, an interval in a profiler
            # trace) — the same boundaries, zero new syncs
            from .obs.attrib import (
                STAGE_TICK_DISPATCH,
                STAGE_TICK_EMIT,
                STAGE_TICK_REBUILD,
                STAGE_TICK_TX_DRAIN,
                get_attrib,
            )

            _att = get_attrib()
            self._att_tick = {
                "dispatch": _att.clock(STAGE_TICK_DISPATCH),
                "rebuild": _att.clock(STAGE_TICK_REBUILD),
                "tx_drain": _att.clock(STAGE_TICK_TX_DRAIN),
                "emit": _att.clock(STAGE_TICK_EMIT),
            }
        else:
            from .obs.attrib import NULL_CLOCK

            self._tracer = None
            self._trace = None
            self._decisions = None
            self._att_tick = dict.fromkeys(("dispatch", "rebuild", "tx_drain", "emit"), NULL_CLOCK)
        # the host side of each ingest dispatch and each state save, with
        # what they moved (records and padded slots; bytes written)
        from .obs.attrib import STAGE_INGEST_DISPATCH, STAGE_STATE_SAVE, get_attrib

        self._att_ingest = get_attrib().clock(STAGE_INGEST_DISPATCH, counters=("records", "slots"))
        self._att_save = get_attrib().clock(STAGE_STATE_SAVE, counters=("bytes",), shared=True)
        self._refresh_params()
        # emission pipelining (tpuEngine.asyncEmission / the async_emission
        # kwarg; default OFF): hold each tick's TickEmission and fetch it
        # while the NEXT tick's dispatch is in flight, overlapping the
        # device->host readback + host fan-out with device compute (CPU and
        # TPU dispatch are both async). Costs one tick of emission/alert
        # latency — a replay/catch-up throughput mode, never the default
        # (the <100 ms detection budget is per-tick).
        if async_emission is None:
            async_emission = bool(
                apm_config.get("tpuEngine", {}).get("asyncEmission", False)
            )
        self._async_emission = async_emission
        self._pending_emission: Optional[Tuple[int, TickEmission, int]] = None
        # jax.jit memoizes per static EngineConfig, so growth (a new cfg)
        # recompiles automatically through these two callables
        self._step = make_engine_step(self.cfg)
        self._ingest = jax.jit(engine_ingest, static_argnums=1, donate_argnums=(0,))
        # the fused executor folds the staggered-rebuild chunk into the tick
        # program; only the staged executor owes the separate scheduler
        self._rebuild_sched = (
            None if self._step.rebuild_integrated else RebuildScheduler(self.cfg)
        )

    # -- params / growth -----------------------------------------------------
    def _refresh_params(self) -> None:
        zcfg = self.apm_config.get("streamCalcZScore", {})
        acfg = self.apm_config.get("streamProcessAlerts", {})
        lag_values = [spec.lag for spec in self.cfg.lags]
        np_dtype = self._np_dtype()
        zparams = self.registry.zscore_params(zcfg, lag_values, dtype=np_dtype)
        aparams = self.registry.alert_params(acfg, dtype=np_dtype)
        eparams = self.registry.ewma_params(
            self.apm_config.get("tpuEngine", {}), self.cfg.ewma, dtype=np_dtype
        )
        self.params = EngineParams(
            thresholds=tuple(jnp.asarray(zparams[l]["threshold"]) for l in lag_values),
            influences=tuple(jnp.asarray(zparams[l]["influence"]) for l in lag_values),
            hard_max_ms=jnp.asarray(aparams["hard_max_ms"]),
            suppressed=jnp.asarray(aparams["suppressed"]),
            active=jnp.asarray(np.arange(self.cfg.capacity) < self.registry.count),
            ewma_thresholds=tuple(
                jnp.asarray(eparams[spec.channel_id]["threshold"]) for spec in self.cfg.ewma
            ),
            ewma_influences=tuple(
                jnp.asarray(eparams[spec.channel_id]["influence"]) for spec in self.cfg.ewma
            ),
        )
        self._params_registry_count = self.registry.count
        if self._telemetry:
            # decision-record inputs (obs/decisions): the exact host vectors
            # the device params were built from, keyed by channel id (lag
            # value for z-score channels, negative channel_id for EWMA)
            self._host_thresholds = {
                int(l): zparams[l]["threshold"] for l in lag_values
            }
            self._host_influences = {
                int(l): zparams[l]["influence"] for l in lag_values
            }
            for spec in self.cfg.ewma:
                self._host_thresholds[spec.channel_id] = eparams[spec.channel_id]["threshold"]
                self._host_influences[spec.channel_id] = eparams[spec.channel_id]["influence"]
            self._lag_index = {int(spec.lag): i for i, spec in enumerate(self.cfg.lags)}
            self._ewma_index = {spec.channel_id: i for i, spec in enumerate(self.cfg.ewma)}
        if self._tracer is not None:
            self._m_capacity.set(self.cfg.capacity)
            self._m_services.set(self.registry.count)

    def note_intake_time(self, ingest_ts: Optional[float]) -> None:
        """Record a message's transport ingest stamp (header ``ingest_ts``);
        the oldest outstanding stamp anchors the ingest->emit/alert latency
        observed at the next emission. Benign-racy min (GIL-atomic reads):
        called from the broker consumer thread while the device thread
        resets it."""
        if ingest_ts is None or self._tracer is None:
            return
        cur = self._intake_oldest_ts
        if cur is None or ingest_ts < cur:
            self._intake_oldest_ts = ingest_ts

    def note_trace(
        self,
        trace_id: str,
        server: str,
        service: str,
        label: int,
        start: float,
        end: Optional[float] = None,
        **attrs,
    ) -> None:
        """Register one SAMPLED in-flight transaction (the worker's feed
        boundary). Records the ``feed`` span (transport delivery -> device
        absorb) and keeps the trace live until the tick that closes its
        bucket emits — _process_emission then records the tick/emit (and
        alert, when fired) spans under the same trace_id. Called only for
        the 1/rate sampled messages, never on the per-message hot path."""
        if self._trace is None:
            return
        end = time.time() if end is None else end
        self._trace.span(
            trace_id, "feed", start, end,
            server=server, service=service, label=int(label), **attrs,
        )
        self._live_traces.append(
            {
                "trace_id": trace_id,
                "server": server,
                "service": service,
                "label": int(label),
            }
        )

    def apply_config(self, apm_config: dict) -> None:
        """Hot-reload hook: re-derive per-row params (thresholds, overrides,

        suppression) without touching device state — the live-actionable
        subset, like the reference's watcher callbacks (§5.6)."""
        self.apm_config = apm_config
        self._refresh_params()
        if self.alerts_manager is not None:
            self.alerts_manager.set_config(apm_config.get("streamProcessAlerts", {}))

    def _grow(self) -> None:
        self.drain_emission()  # pending emission belongs to the old capacity
        new_capacity = self.cfg.capacity * 2
        if self.logger:
            self.logger.warning(f"Growing service capacity {self.cfg.capacity} -> {new_capacity} (recompile)")
        self.registry = self.registry.grown(new_capacity)
        stats_state, stats_cfg = dstats.grow_state(self.state.stats, self.cfg.stats, new_capacity)
        zstates = []
        for i, spec in enumerate(self.cfg.lags):
            zs, _ = dzscore.grow_state(
                self.state.zscores[i], zscore_cfg(self.cfg, spec), new_capacity
            )
            zstates.append(zs)
        pad_n = new_capacity - self.cfg.capacity
        counters = tuple(jnp.pad(c, (0, pad_n)) for c in self.state.alert_counters)
        estates = tuple(dewma.grow_state(e, new_capacity) for e in self.state.ewmas)
        ecounters = tuple(jnp.pad(c, (0, pad_n)) for c in self.state.ewma_counters)
        self.cfg = self.cfg._replace(stats=stats_cfg)
        self.state = EngineState(stats_state, tuple(zstates), counters, estates, ecounters)
        # the staged step closes over cfg (capacity changed: new programs);
        # the rebuild rotation restarts at chunk 0 — harmless (idempotent)
        self._step = make_engine_step(self.cfg)
        self._rebuild_sched = (
            None if self._step.rebuild_integrated else RebuildScheduler(self.cfg)
        )
        if self._tracer is not None:
            self._m_grows.inc()
        self._refresh_params()

    def _row_for(self, server: str, service: str) -> int:
        try:
            return self.registry.lookup_or_add(server, service)
        except CapacityExceeded:
            self._flush_pending()
            self._grow()
            return self.registry.lookup_or_add(server, service)

    # -- feed ----------------------------------------------------------------
    def feed(self, tx: TxEntry) -> None:
        """One transaction (consumeMsg parity, stream_calc_stats.js:331-371)."""
        if math.isnan(tx.end_ts) or math.isnan(tx.elapsed):
            # malformed numerics are rejected at intake: a stored NaN sample
            # would poison window sums AND make the percentile basis depend
            # on the impl's NaN ordering (sort vs top_k)
            if self.logger:
                self.logger.error(f"NaN end_ts/elapsed in txEntry, dropped: {tx}")
            return
        label = int(tx.end_ts) // 10000
        # host-side label mirror: avoids a device->host sync per message
        if label > self._latest_label:
            self._flush_pending()
            self._run_tick(label)
            self._latest_label = label
        row = self._row_for(tx.server, tx.service)
        self._pending.append((row, label, float(tx.elapsed)))
        if self.on_ordered_tx is not None:
            self.heap.push(tx)
        elif self.on_ordered_csv is not None:  # mixed callers: feed() must
            # serve the CSV drain too, not only feed_csv_batch
            self._tx_backlog.append((float(tx.end_ts), tx.to_csv()))
        if len(self._pending) >= self.micro_batch_size:
            self._flush_pending()

    def feed_batch(self, txs: Sequence[TxEntry]) -> None:
        for tx in txs:
            self.feed(tx)

    def feed_csv_batch(self, lines: Sequence[str]) -> int:
        """Bulk host fast path: decode ``tx|...`` pipe-CSV lines with numpy
        split/astype and ingest them as arrays, skipping TxEntry objects, the
        per-entry heap push, and the per-tuple pending list entirely.

        Emissions are identical to feeding line-by-line: arrival order is
        kept, and ticks fire exactly where feed() would fire them — before
        each entry whose label exceeds every label seen so far (the
        stats-before-addData event order, stream_calc_stats.js:348-370).
        Entries between two ticks are scattered as one array batch. Returns
        the number of transactions ingested. Falls back to the object path
        when an ordered-tx consumer needs the heap.
        """
        if self.on_ordered_tx is not None:
            from .entries import EntryFactory

            fac = EntryFactory()
            n = 0
            for line in lines:
                entry = fac.from_csv(line)
                if entry is not None and entry.type == "tx":
                    self.feed(entry)
                    n += 1
                elif self.logger:
                    self.logger.info(f"Not a transactions entry: {line[:200]}")
            return n

        dec = self._decoder()
        if dec is not None:
            return self.feed_csv_bytes("\n".join(lines).encode("utf-8"))

        good = []
        good_lines: List[str] = []
        n_bad = 0
        for line in lines:
            p = line.split("|")
            if len(p) == 9 and p[0] == "tx":
                good.append(p)
                good_lines.append(line)
            else:
                n_bad += 1
        if n_bad and self.logger:
            self.logger.info(f"Skipped {n_bad} non-tx/malformed lines in batch")
        if not good:
            return 0
        fields = np.array(good, dtype=object)  # [N, 9] strings
        # numpy float parsing accepts forms JS parseInt does not ('1e5',
        # 'inf', '1_0'); the wire is explicitly interoperable, so rows whose
        # numerics are not plain decimals take the js_parse_int slow path to
        # keep this batch path's labels identical to feed()'s
        plain = np.fromiter(
            (
                bool(_PLAIN_NUMBER.match(p[6])) and bool(_PLAIN_NUMBER.match(p[7]))
                for p in good
            ),
            bool,
            len(good),
        )
        from .entries import js_parse_int

        if plain.all():
            end_ts = fields[:, 6].astype(np.float64)
            elaps = fields[:, 7].astype(np.float64)
        else:
            end_ts = np.empty(len(good), np.float64)
            elaps = np.empty(len(good), np.float64)
            pi = np.nonzero(plain)[0]
            if len(pi):
                end_ts[pi] = fields[pi, 6].astype(np.float64)
                elaps[pi] = fields[pi, 7].astype(np.float64)
            for i in np.nonzero(~plain)[0]:
                end_ts[i] = js_parse_int(fields[i, 6])
                elaps[i] = js_parse_int(fields[i, 7])
        end_ts = np.trunc(end_ts)  # TxEntry applies js_parse_int (int truncation)
        elaps = np.trunc(elaps)
        ok = ~np.isnan(end_ts) & ~np.isnan(elaps)  # same intake filter as feed()
        n_nan = int(len(end_ts) - ok.sum())
        if n_nan:
            if self.logger:
                self.logger.error(f"NaN end_ts/elapsed in batch: {n_nan} lines dropped")
            fields, end_ts, elaps = fields[ok], end_ts[ok], elaps[ok]
            good_lines = [gl for gl, o in zip(good_lines, ok) if o]
            if len(fields) == 0:
                return 0
        labels = (end_ts.astype(np.int64) // 10000).astype(np.int32)
        keys = np.array(
            [s + "\x00" + v for s, v in zip(fields[:, 1], fields[:, 2])]
        )

        def resolve_rows(lo: int, hi: int) -> np.ndarray:
            # registry rows for one segment: each unique (server, service)
            # resolved once. Per-SEGMENT (not per-batch) so a tick only ever
            # sees services registered by entries processed before it, and
            # new keys register in FIRST-APPEARANCE order (np.unique sorts,
            # which would permute emission row order vs feed())
            uk, first_idx, inv = np.unique(
                keys[lo:hi], return_index=True, return_inverse=True
            )
            rowmap = np.empty(len(uk), np.int32)
            for j in np.argsort(first_idx, kind="stable"):
                rowmap[j] = self._row_for(*uk[j].split("\x00", 1))
            return rowmap[inv]

        track_ordered = self.on_ordered_csv is not None
        ets_list = end_ts.tolist() if track_ordered else None

        def backlog(lo: int, hi: int) -> None:
            self._tx_backlog.extend(zip(ets_list[lo:hi], good_lines[lo:hi]))

        self._walk_tick_segments(
            labels,
            lambda lo, hi: self._ingest_arrays(
                resolve_rows(lo, hi), labels[lo:hi], elaps[lo:hi]
            ),
            backlog if track_ordered else None,
        )
        return len(labels)

    def _walk_tick_segments(self, labels: np.ndarray, ingest_segment, backlog_segment) -> None:
        """Shared tick-ordering walk for the bulk intake paths.

        Ticks fire exactly where feed() would fire them: before each entry
        whose label exceeds every label seen so far — INCLUDING the pre-batch
        latest. Without the floor, a batch that is internally increasing but
        wholly below the resumed latest (stale backfill after a restart)
        would tick backward and regress the label mirror (caught by the soak
        test's mid-run kill/restore). Entries between two ticks form one
        segment: ``backlog_segment(lo, hi)`` (if given) then
        ``ingest_segment(lo, hi)`` run before the tick that follows them."""
        self._flush_pending()  # interleaved feed() entries must not reorder
        running_max = np.maximum(np.maximum.accumulate(labels), self._latest_label)
        prior = np.concatenate([[self._latest_label], running_max[:-1]])
        tick_points = np.nonzero(running_max > prior)[0]
        idx = 0
        for i in tick_points:
            i = int(i)
            if i > idx:
                if backlog_segment is not None:
                    backlog_segment(idx, i)
                ingest_segment(idx, i)
                idx = i
            label = int(labels[i])
            self._run_tick(label)
            self._latest_label = label
        if backlog_segment is not None:
            backlog_segment(idx, len(labels))
        ingest_segment(idx, len(labels))

    def _reset_decode_map(self) -> None:
        # decoder-id -> registry row; -1 = interned but never registered (the
        # id's records were all NaN-dropped so far — the numpy path would not
        # have registered that key either). _decode_keys caches the decoder's
        # id -> (server, service) strings, fetched incrementally.
        self._decode2row = np.full(256, -1, np.int32)
        self._decode_keys: List[Tuple[str, str]] = []

    def _decoder(self):
        """The native batch decoder, created lazily; None when disabled or
        the toolchain is unavailable (callers fall back to the numpy path)."""
        if not self._use_native_decode:
            return None
        if not self._native_dec_tried:
            self._native_dec_tried = True
            try:
                from .native import TxDecoder

                self._native_dec = TxDecoder()
                self._reset_decode_map()
            except Exception as e:
                self._native_dec = None
                if self.logger:
                    self.logger.info(f"native decoder unavailable, using numpy path: {e}")
        return self._native_dec

    def feed_csv_bytes(self, blob: bytes) -> int:
        """Bulk intake of a newline-separated ``tx|...`` byte blob through the
        native decoder — one C++ pass instead of per-line Python string ops.
        Emission/tick semantics are identical to :meth:`feed_csv_batch`
        (asserted by tests/test_native.py parity tests). Falls back to the
        numpy path when the native decoder is unavailable."""
        dec = self._decoder()
        if dec is None or self.on_ordered_tx is not None:
            return self.feed_csv_batch(blob.decode("utf-8", "replace").splitlines())

        from .entries import js_parse_int

        end_ts, elaps, keyids, offs, lens, flags, n_bad = dec.decode(blob)
        if n_bad and self.logger:
            self.logger.info(f"Skipped {n_bad} non-tx/malformed lines in batch")
        if len(end_ts) == 0:
            return 0
        # exotic numerics (non-ASCII bytes): re-parse with the reference
        # implementation so the native path cannot silently diverge
        for i in np.nonzero(flags & 1)[0]:
            o, l = int(offs[i]), int(lens[i])
            p = blob[o : o + l].decode("utf-8", "replace").split("|")
            end_ts[i] = js_parse_int(p[6])
            elaps[i] = js_parse_int(p[7])
        ok = ~np.isnan(end_ts) & ~np.isnan(elaps)  # same intake filter as feed()
        n_nan = int(len(end_ts) - ok.sum())
        if n_nan:
            if self.logger:
                self.logger.error(f"NaN end_ts/elapsed in batch: {n_nan} lines dropped")
            end_ts, elaps, keyids = end_ts[ok], elaps[ok], keyids[ok]
            offs, lens = offs[ok], lens[ok]
            if len(end_ts) == 0:
                return 0
        labels = (end_ts.astype(np.int64) // 10000).astype(np.int32)

        track_ordered = self.on_ordered_csv is not None
        if track_ordered:
            ets_list = end_ts.tolist()
            # ASCII blob (the wire norm): byte offsets == str offsets, so one
            # whole-blob decode + str slicing replaces per-line bytes.decode
            text = blob.decode("ascii") if blob.isascii() else None
            offs_l = offs.tolist()
            lens_l = lens.tolist()

        def backlog(lo: int, hi: int) -> None:
            if text is not None:
                self._tx_backlog.extend(
                    (ets_list[j], text[offs_l[j] : offs_l[j] + lens_l[j]])
                    for j in range(lo, hi)
                )
            else:
                self._tx_backlog.extend(
                    (ets_list[j], blob[offs_l[j] : offs_l[j] + lens_l[j]].decode("utf-8", "replace"))
                    for j in range(lo, hi)
                )

        self._walk_tick_segments(
            labels,
            lambda lo, hi: self._ingest_arrays(
                self._resolve_decoded_rows(keyids[lo:hi]), labels[lo:hi], elaps[lo:hi]
            ),
            backlog if track_ordered else None,
        )
        return len(labels)

    def feed_frames(self, blob: bytes) -> int:
        """Bulk intake of one packed APF1 frame batch (transport.frameMode).

        The frame's lines region already IS the newline-separated
        ``tx|...`` byte blob the bulk decoder wants, so a frame feed is a
        header check plus one slice into :meth:`feed_csv_bytes` — zero
        per-record objects between the parser's emitter and the columnar
        ingest. Raises ``FrameError`` on a corrupt header (callers treat
        it like any bad batch: count, log, drop)."""
        from .transport import frames as _frames

        region = _frames.lines_region(blob)
        if len(region) == 0:
            return 0
        return self.feed_csv_bytes(bytes(region))

    def _resolve_decoded_rows(self, seg_ids: np.ndarray) -> np.ndarray:
        """Registry rows for one tick segment of decoder key ids.

        A key registers at its first id that actually reaches a segment
        (post NaN-filter) — NOT at interning time, because the decoder
        interns tx-shaped lines whose numerics turn out unparseable, and the
        numpy path never registers those phantom keys. Unregistered ids stay
        -1 in the map until a surviving record arrives. Decoder ids are
        assigned in first-appearance order, so registering a segment's
        unmapped ids in ascending id order IS the numpy path's
        first-appearance registration order."""
        if seg_ids.size == 0:
            return seg_ids.astype(np.int32)
        top = int(seg_ids.max()) + 1
        known = len(self._decode_keys)
        if top > known:
            self._decode_keys.extend(self._native_dec.keys_from(known))
            if len(self._decode_keys) > len(self._decode2row):
                grown = np.full(
                    max(len(self._decode_keys), 2 * len(self._decode2row)), -1, np.int32
                )
                grown[: len(self._decode2row)] = self._decode2row
                self._decode2row = grown
        rows = self._decode2row[seg_ids]
        unmapped = rows == -1
        if unmapped.any():
            # register in FIRST-APPEARANCE order within the segment (not
            # ascending id): a phantom-interned key re-appearing valid after
            # a newer key must register after it, exactly as the numpy path
            # (which never saw the phantom) would
            uk, first_idx = np.unique(seg_ids[unmapped], return_index=True)
            for j in np.argsort(first_idx, kind="stable"):
                i = int(uk[j])
                self._decode2row[i] = self._row_for(*self._decode_keys[i])
            rows = self._decode2row[seg_ids]
        return rows

    def _ingest_arrays(self, rows: np.ndarray, labels: np.ndarray, elaps: np.ndarray) -> None:
        """Scatter pre-decoded arrays in micro_batch_size chunks, with the
        SAME two pad tiers as _flush_pending (small tier for sub-256
        segments, full tier otherwise) so a trickle-sized bulk feed — the
        at-least-once batched intake, tick-boundary segments — doesn't pay
        a micro_batch_size-wide scatter per segment, and both paths share
        the same two compiled ingest variants."""
        B = self.micro_batch_size
        small = min(256, B)
        dtype = self._np_dtype()
        if self._delta_track:
            self._mark_cells(rows, labels)
        clk = self._att_ingest
        for i in range(0, len(rows), B):
            m = min(B, len(rows) - i)
            pad = small if m <= small else B
            with clk.span():
                r = np.zeros(pad, np.int32)
                l = np.zeros(pad, np.int32)
                e = np.zeros(pad, dtype)
                v = np.zeros(pad, bool)
                r[:m] = rows[i : i + m]
                l[:m] = labels[i : i + m]
                e[:m] = elaps[i : i + m]
                v[:m] = True
                self.state = self._ingest(self.state, self.cfg, r, l, e, v)
            clk.count("records", m)
            clk.count("slots", pad)
            if self._tracer is not None:
                self._m_tx.inc(m)
                self._m_pending_batch.observe(m)

    def flush(self) -> None:
        self._flush_pending()
        self.drain_emission()

    def drain_emission(self) -> None:
        """Deliver the held tick emission (async-emission mode). No-op when
        nothing is pending; callers that need every callback delivered
        (flush, snapshot, shutdown) route through here."""
        if self._pending_emission is not None:
            label, emission, count = self._pending_emission
            self._pending_emission = None
            self._process_emission(label, emission, count)

    def _flush_pending(self) -> None:
        if not self._pending:
            return
        ingest = self._ingest
        # TWO pad tiers: a small one for sparse tick-boundary flushes (~10
        # records must not pay a micro_batch_size-wide scatter — the ingest
        # program's cost scales with the padded width) and the full
        # micro-batch tier. Exactly two compiled variants: each extra tier
        # costs a ~1 s XLA:CPU compile on first use, which a replay-style
        # run pays INSIDE its measured window.
        n = len(self._pending)
        small = min(256, self.micro_batch_size)
        pad = small if n <= small else max(self.micro_batch_size, n)
        clk = self._att_ingest
        with clk.span():
            rows = np.zeros(pad, np.int32)
            labels = np.zeros(pad, np.int32)
            elaps = np.zeros(pad, self._np_dtype())
            valid = np.zeros(pad, bool)
            r_t, l_t, e_t = zip(*self._pending)  # column fill, no per-tuple loop
            rows[:n] = r_t
            labels[:n] = l_t
            elaps[:n] = e_t
            valid[:n] = True
            if self._delta_track:
                self._mark_cells(rows[:n], labels[:n])
            self._pending.clear()
            self.state = ingest(self.state, self.cfg, rows, labels, elaps, valid)
        clk.count("records", n)
        clk.count("slots", pad)
        if self._tracer is not None:
            self._m_tx.inc(n)
            self._m_pending_batch.observe(n)

    def _np_dtype(self):
        return np.float64 if self.cfg.stats.dtype == jnp.float64 else np.float32

    # -- tick ----------------------------------------------------------------
    def _run_tick(self, new_label: int) -> None:
        if self._delta_track:
            # delta capture derives the stats ring-advance, the z ring push
            # positions and the EWMA season slots from the tick-label
            # sequence alone — no per-tick readback
            self._delta_ticks.append(int(new_label))
        tr = self._tracer
        clk = self._att_tick
        # trace plane: a tick with live sampled traces notes its wall window
        # so their "tick" span describes the tick that closed their bucket
        # (looked up by label at emission — exact under async-emission too).
        # A tick with no live traces pays one truthiness check.
        trace_tick = self._trace is not None and bool(self._live_traces)
        if trace_tick:
            tick_wall_start = time.time()
        if tr is not None:
            # catch-up depth: labels advanced by this tick (1 = steady state;
            # >1 = replay/backfill jump — the megatick-candidate signal)
            catchup = new_label - self._latest_label if self._latest_label else 1
            t0 = time.perf_counter()
        with clk["dispatch"].span():
            if self.registry.count != self._params_registry_count:
                # newly registered services activate (z-score warm-up starts) at
                # the next tick boundary — the reference's per-key list creation
                self._refresh_params()
            emission, self.state = self._step(self.state, new_label, self.params)
        t1 = time.perf_counter() if tr is not None else 0.0
        with clk["rebuild"].span():
            if self._rebuild_sched is not None:
                # staggered exact rebuild of the sliding z-score aggregates: one
                # row chunk per tick on a rotating schedule (RebuildScheduler) —
                # the staged executor's companion; the fused executor folds the
                # chunk into the tick program instead (rebuild_integrated).
                self.state = self._rebuild_sched.step(self.state)
        t2 = time.perf_counter() if tr is not None else 0.0
        with clk["tx_drain"].span():
            edge_ts = dstats.edge_ts_ms(new_label, self.cfg.stats)

            # ordered tx drain to DB (heap pop up to edge timestamp)
            if self.on_ordered_tx is not None:
                for tx in self.heap.pop_all_leq(edge_ts):
                    self.on_ordered_tx(tx)
            else:
                self.heap.pop_all_leq(edge_ts)
            # fast-path drain: due raw lines, end_ts-sorted (stable: arrival order
            # within equal timestamps), one C-speed sort per tick instead of
            # per-entry heap pushes
            if self.on_ordered_csv is not None and self._tx_backlog:
                due = [p for p in self._tx_backlog if p[0] <= edge_ts]
                if due:
                    self._tx_backlog = [p for p in self._tx_backlog if p[0] > edge_ts]
                    due.sort(key=lambda p: p[0])
                    for _ts, line in due:
                        self.on_ordered_csv(line)

        t3 = time.perf_counter() if tr is not None else 0.0
        with clk["emit"].span():
            if trace_tick:
                self._tick_walls[new_label] = (tick_wall_start, time.time())
                if len(self._tick_walls) > 8:  # bounded: emission pops its label;
                    # a zero-row emission (count==0) leaves one behind — prune
                    for stale in sorted(self._tick_walls)[:-8]:
                        self._tick_walls.pop(stale, None)
            if self._async_emission:
                # double-buffered readback: hold this tick's emission; deliver
                # the PREVIOUS one now, while this tick's programs are still in
                # flight on the device. Per-tick callback order (stats ->
                # fullstats -> alerts) is preserved; the ordered-tx drain above
                # stays immediate (host-only bookkeeping, different queue).
                # Registry count snapshots at dispatch: rows registered later
                # did not exist at this tick and must not emit for it.
                prev, self._pending_emission = (
                    self._pending_emission,
                    (new_label, emission, self.registry.count),
                )
                if prev is not None:
                    self._process_emission(*prev)
            else:
                self._process_emission(new_label, emission, self.registry.count)
        if tr is not None:
            tr.record(new_label, {
                "dispatch": t1 - t0,
                "rebuild": t2 - t1,
                "tx_drain": t3 - t2,
                "emit": time.perf_counter() - t3,
            }, catchup_labels=catchup)

    # apm: sync-boundary: THE emit readback — the one blocking sync per tick the cost model budgets for (async emission overlaps it with the next dispatch)
    def _process_emission(self, new_label: int, emission: TickEmission, count: int) -> None:
        """Device->host readback + host fan-out of one tick's emission
        (StatEntry/FullStatEntry/alert callbacks). Split from _run_tick so
        async-emission mode can run it one tick late."""
        edge_ts = dstats.edge_ts_ms(new_label, self.cfg.stats)
        if count == 0:
            return
        # claim the oldest outstanding transport stamp for THIS emission
        # (async mode delivers one tick late, so the stamp honestly includes
        # the pipelining delay the operator is paying); claimed only by an
        # emission that actually fans out — a zero-row tick leaves it for
        # the first real one
        self._emitting_intake_ts, self._intake_oldest_ts = self._intake_oldest_ts, None
        # claim the sampled traces whose bucket this tick closed (labels
        # below new_label); later labels stay live for their own tick. The
        # claimed set is matched against alerts during the fan-out below.
        if self._trace is not None and self._live_traces:
            keep: deque = deque(maxlen=self._live_traces.maxlen)
            claimed: List[dict] = []
            for t in self._live_traces:
                (claimed if t["label"] < new_label else keep).append(t)
            self._live_traces = keep
            self._emitting_traces = claimed
        else:
            self._emitting_traces = ()
        self._emit_wall_start = time.time()
        # np.asarray(whole)[:count], never np.asarray(x[:count]): slicing a
        # jax array dispatches a compiled gather per call (~1.2 ms each on
        # CPU), and this path runs 3 + 6*channels of them per tick — the
        # numpy copy of the full row axis is microseconds by comparison
        tpm = np.asarray(emission.tpm)[:count]
        metrics = np.asarray(emission.average)[:count]  # [count, 3]

        emit_landed = time.time()
        if self._tracer is not None and self._emitting_intake_ts is not None:
            # the readback above (np.asarray of the emission) has landed: the
            # tick's results are host-visible — the "emit" moment
            lat = emit_landed - self._emitting_intake_ts
            if self._emitting_traces:
                # OpenMetrics exemplar: the latency bucket points at a trace
                # that actually lived through this emission
                self._m_emit_lat.observe_exemplar(lat, self._emitting_traces[0]["trace_id"])
            else:
                self._m_emit_lat.observe(lat)
        if self._emitting_traces:
            tick_wall = self._tick_walls.pop(new_label, None)
            for t in self._emitting_traces:
                if tick_wall is not None:
                    self._trace.span(
                        t["trace_id"], "tick", tick_wall[0], tick_wall[1],
                        label=new_label, service=t["service"],
                    )
                self._trace.span(
                    t["trace_id"], "emit", self._emit_wall_start, emit_landed,
                    label=new_label, service=t["service"], rows=count,
                )

        n_overflowed = int(np.asarray(emission.overflowed)[:count].sum())
        if n_overflowed:
            self.overflow_rows_total += n_overflowed
            self.overflow_ticks += 1
            if self._tracer is not None:
                self._m_overflow_rows.inc(n_overflowed)
            if self.on_overflow is not None:
                self.on_overflow(new_label, n_overflowed)
            if self.logger and self.overflow_ticks - self._overflow_last_logged_tick >= 30:
                self._overflow_last_logged_tick = self.overflow_ticks
                self.logger.warning(
                    f"Percentile reservoir overflow: {n_overflowed} rows this tick "
                    f"({self.overflow_rows_total} row-ticks total) exceeded "
                    f"samplesPerBucket={self.cfg.stats.samples_per_bucket}; percentiles "
                    f"for those rows are reservoir estimates (bounded error). Raise "
                    f"tpuEngine.samplesPerBucket to restore exactness."
                )

        # .tolist() ONCE per array: row loops below then touch plain Python
        # floats — float(arr[row]) per field costs a numpy scalar box each
        # (measured ~2M boxings per replay run before batching)
        tpm_l = tpm.tolist()
        metrics_l = metrics.tolist()
        if self.on_stat is not None:
            key_of = self.registry.key_of
            for row in range(count):
                server, service = key_of(row)
                mr = metrics_l[row]
                self.on_stat(
                    StatEntry(edge_ts, server, service, tpm_l[row], mr[0], mr[1], mr[2])
                )

        # lag windows + EWMA/seasonal channels share the emission path; EWMA
        # channels ride the FullStatEntry wire with lag = channel_id (<0)
        channels = [(spec.lag, em) for spec, em in zip(self.cfg.lags, emission.lags)]
        channels += [(spec.channel_id, em) for spec, em in zip(self.cfg.ewma, emission.ewma)]
        need_fs = self.on_fullstat is not None
        need_csv = self.on_fullstat_csv is not None
        need_alert = self.on_alert is not None or self.alerts_manager is not None
        for chan_id, lag_em in channels:
            if not (need_fs or need_csv or need_alert):
                continue
            wavg = np.asarray(lag_em.window_avg)[:count]
            lb = np.asarray(lag_em.lower_bound)[:count]
            ub = np.asarray(lag_em.upper_bound)[:count]
            sig = np.asarray(lag_em.signal)[:count]
            trig = np.asarray(lag_em.trigger)[:count]
            bits = np.asarray(lag_em.cause_bits)[:count]
            w_l, lo_l, up_l, sg_l = wavg.tolist(), lb.tolist(), ub.tolist(), sig.tolist()
            key_of = self.registry.key_of

            def make_fs(row: int) -> FullStatEntry:
                server, service = key_of(row)
                mr, wr, lr, ur, sr = (
                    metrics_l[row], w_l[row], lo_l[row], up_l[row], sg_l[row]
                )
                return FullStatEntry(
                    edge_ts, server, service, tpm_l[row], chan_id,
                    mr[0], wr[0], lr[0], ur[0], sr[0],
                    mr[1], wr[1], lr[1], ur[1], sr[1],
                    mr[2], wr[2], lr[2], ur[2], sr[2],
                )

            if need_csv:
                self.on_fullstat_csv(
                    self._format_fullstat_lines(edge_ts, chan_id, count, tpm, metrics, wavg, lb, ub, sig)
                )
            if need_fs:
                for row in range(count):
                    fs = make_fs(row)
                    self.on_fullstat(fs)
                    if need_alert and trig[row]:
                        self._dispatch_alert(fs, int(bits[row]), row=row)
            elif need_alert:
                # alert-only fast path: build objects for triggered rows only
                for row in np.nonzero(trig)[0]:
                    self._dispatch_alert(make_fs(int(row)), int(bits[row]), row=int(row))

    def _trace_for_alert(self, fs: FullStatEntry) -> Optional[str]:
        """trace_id of a claimed (this-emission) sampled trace matching the
        alert's (server, service), or None. Alert-path only."""
        for t in self._emitting_traces:
            if t["service"] == fs.service and t["server"] == fs.server:
                return t["trace_id"]
        return None

    # apm: sync-boundary: alert-path only — one ring-fill scalar read per dispatched alert for decision provenance, never per tick
    def _window_occupancy(self, chan_id, row: int) -> Optional[int]:
        """Ring fill (lag channels) / max slot update count (EWMA channels)
        for one row — a device readback, paid on the ALERT path only."""
        try:
            i = self._lag_index.get(chan_id)
            if i is not None:
                return int(np.asarray(self.state.zscores[i].fill)[row])
            i = self._ewma_index.get(chan_id)
            if i is not None:
                return int(np.asarray(self.state.ewmas[i].count)[row].max())
        except Exception:
            pass
        return None

    def _record_decision(self, fs: FullStatEntry, bits: int, row: Optional[int],
                         trace_id: Optional[str]) -> None:
        """Alert decision provenance (obs/decisions): the z inputs behind
        this page — triggering values, window means, the bands actually
        compared, smoothed signals, configured threshold/influence, window
        occupancy, device cause bits — keyed by trace_id when the bucket
        carried a sampled trace. A failure here must never lose the alert."""
        try:
            chan_id = fs.lag
            thr = infl = None
            if row is not None:
                tv = self._host_thresholds.get(chan_id)
                iv = self._host_influences.get(chan_id)
                if tv is not None and row < len(tv):
                    thr = float(tv[row])
                if iv is not None and row < len(iv):
                    infl = float(iv[row])
            self._decisions.record(
                {
                    "trace_id": trace_id,
                    "ts": time.time(),
                    "edge_ts": int(fs.timestamp),
                    "server": fs.server,
                    "service": fs.service,
                    "channel": chan_id,
                    "row": row,
                    "cause_bits": bits,
                    "cause": dalerts.cause_string(bits),
                    "threshold": thr,
                    "influence": infl,
                    "window_occupancy": self._window_occupancy(chan_id, row)
                    if row is not None else None,
                    "tpm": fs.tpm,
                    "metrics": {
                        "average": {
                            "value": fs.average, "window_mean": fs.average_avg,
                            "lower": fs.average_lb, "upper": fs.average_ub,
                            "signal": fs.average_signal,
                        },
                        "per75": {
                            "value": fs.per75, "window_mean": fs.per75_avg,
                            "lower": fs.per75_lb, "upper": fs.per75_ub,
                            "signal": fs.per75_signal,
                        },
                        "per95": {
                            "value": fs.per95, "window_mean": fs.per95_avg,
                            "lower": fs.per95_lb, "upper": fs.per95_ub,
                            "signal": fs.per95_signal,
                        },
                    },
                }
            )
        except Exception:
            if self.logger:
                self.logger.exception("Decision record failed (alert still dispatched)")

    def _dispatch_alert(self, fs: FullStatEntry, bits: int, row: Optional[int] = None) -> None:
        trace_id = None
        if self._tracer is not None:
            self._m_alerts.inc()
            trace_id = self._trace_for_alert(fs) if self._emitting_traces else None
            if self._emitting_intake_ts is not None:
                lat = time.time() - self._emitting_intake_ts
                if trace_id is not None:
                    self._m_alert_lat.observe_exemplar(lat, trace_id)
                else:
                    self._m_alert_lat.observe(lat)
            if trace_id is not None:
                # the alert hop of the sampled transaction's trace: emission
                # readback -> this dispatch
                self._trace.span(
                    trace_id, "alert",
                    self._emit_wall_start or time.time(), time.time(),
                    service=fs.service, channel=fs.lag,
                    cause=dalerts.cause_string(bits),
                )
        if self._decisions is not None:
            self._record_decision(fs, bits, row, trace_id)
        if self.alerts_manager is not None:
            alert = self.alerts_manager.process_trigger(fs, bits)
            if alert is not None:
                self.alerts_manager.add_to_buffer(alert)
                if self.on_alert is not None:
                    self.on_alert(alert)
        elif self.on_alert is not None:
            self.on_alert((fs, bits))

    def _format_fullstat_lines(
        self, edge_ts: int, chan_id, count: int, tpm, metrics, wavg, lb, ub, sig
    ) -> List[str]:
        """The tick's FullStat wire lines for one channel, in bulk.

        Byte-identical to ``FullStatEntry(...).to_csv()`` (entries.py wire
        quirks: nf() 1-decimal toFixed, tpm 2-decimal, bare average signal —
        entries.js:117) without constructing 20-field dataclasses per row;
        asserted by tests/test_pipeline.py parity."""
        from .entries import nf

        ts_s = str(int(edge_ts))
        t = tpm.tolist()
        m = metrics.tolist()
        w = wavg.tolist()
        lo = lb.tolist()
        up = ub.tolist()
        sg = sig.tolist()
        key_of = self.registry.key_of
        lines = []
        for row in range(count):
            server, service = key_of(row)
            mr, wr, lr, ur, sr = m[row], w[row], lo[row], up[row], sg[row]
            lines.append(
                f"fs|{ts_s}|{server}|{service}|{chan_id}|{nf(t[row], 2)}|"
                f"{nf(mr[0])}:{nf(wr[0])}:{nf(lr[0])}:{nf(ur[0])}:{sr[0]}|"
                f"{nf(mr[1])}:{nf(wr[1])}:{nf(lr[1])}:{nf(ur[1])}:{nf(sr[1])}|"
                f"{nf(mr[2])}:{nf(wr[2])}:{nf(lr[2])}:{nf(ur[2])}:{nf(sr[2])}"
            )
        return lines

    # -- checkpoint / resume (§5.4) ------------------------------------------
    # apm: sync-boundary: checkpoint serialization reads the full engine state back by contract (epoch cadence, not tick cadence)
    def _capture_resume_arrays(self, delivery: Optional[dict] = None) -> dict:
        """The full-snapshot array dict (save_resume's npz schema, registry
        and pending included) — shared by the atomic npz writer and the
        delta chain's compaction path (deltachain.DeltaChain.compact), which
        writes the same capture as a chain base off the hot path."""
        arrays = {
            "latest_bucket": np.asarray(self.state.stats.latest_bucket),
            "counts": np.asarray(self.state.stats.counts),
            "sums": np.asarray(self.state.stats.sums),
            "samples": np.asarray(self.state.stats.samples),
            "nsamples": np.asarray(self.state.stats.nsamples),
        }
        for i, spec in enumerate(self.cfg.lags):
            z = self.state.zscores[i]
            zvals = np.asarray(z.values)
            if zvals.dtype not in (np.float32, np.float64):
                # bf16 rings: .npz has no portable bfloat16 — store f32
                # (exact upcast; load downcasts back to identical bits)
                zvals = zvals.astype(np.float32)
            arrays[f"z{spec.lag}_values"] = zvals
            arrays[f"z{spec.lag}_fill"] = np.asarray(z.fill)
            arrays[f"z{spec.lag}_pos"] = np.asarray(z.pos)
            arrays[f"z{spec.lag}_counters"] = np.asarray(self.state.alert_counters[i])
        for i, espec in enumerate(self.cfg.ewma):
            e = self.state.ewmas[i]
            # key includes the slot count AND slot width so a SEASON_SLOTS or
            # SLOT_INTERVALS config change invalidates the snapshot (like lag
            # in the z{lag}_* keys) instead of resuming baselines under a
            # wrong-shaped or wrong-meaning slot mapping
            ek = f"e{espec.channel_id}x{espec.season_slots}x{espec.slot_intervals}"
            arrays[f"{ek}_mean"] = np.asarray(e.mean)
            arrays[f"{ek}_var"] = np.asarray(e.var)
            arrays[f"{ek}_count"] = np.asarray(e.count)
            arrays[f"{ek}_counters"] = np.asarray(self.state.ewma_counters[i])
            arrays[f"{ek}_trend"] = np.asarray(e.trend)
        arrays["registry"] = np.array(
            ["\x00".join(k) for k in self.registry.rows()], dtype=object
        )
        # pending ordered-tx records (not yet past the window edge) must
        # survive a restart — the reference keeps its heap in the resume file
        # (stream_calc_stats resume semantics). Stored as wire lines.
        arrays["pending_tx"] = np.array(self._pending_tx_lines(), dtype=object)
        if delivery is None:
            delivery = self.delivery_state
        if delivery is not None:
            # JSON in a 0-d object array: schema-stable regardless of the
            # dedup window's shape, absent entirely for at-most-once workers
            import json as _json

            arrays["delivery_state"] = np.array(_json.dumps(delivery), dtype=object)
            self.delivery_state = delivery
        return arrays

    def _pending_tx_lines(self) -> List[str]:
        pending = [tx.to_csv() for tx in self.heap.items()]
        pending += [line for _ts, line in self._tx_backlog]
        return pending

    def save_resume(self, path: str, *, delivery: Optional[dict] = None) -> None:
        """Atomic snapshot (tmp + rename); `path` is used verbatim — no .npz
        suffix magic — so load_resume(path) always finds what was saved.

        ``delivery`` couples the snapshot to queue position (the at-least-once
        epoch contract): a per-queue dict of {"epoch": watermark, "dedup":
        [recently absorbed msg ids], ...} saved ATOMICALLY WITH the engine
        state that absorbed those messages — the invariant the worker's
        ack-after-checkpoint cycle rests on (a message id is in the saved
        window iff its effect is in the saved tensors)."""
        # a held emission describes a tick already IN the snapshot state; it
        # must reach its consumers now or a restore would silently drop it
        self.drain_emission()
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        import tempfile

        with self._att_save.span():
            arrays = self._capture_resume_arrays(delivery)
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(os.path.abspath(path)), suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as fh:
                    np.savez_compressed(fh, **arrays)
                    self._att_save.count("bytes", fh.tell())
                os.replace(tmp, path)
            except BaseException:
                if os.path.exists(tmp):
                    os.unlink(tmp)
                raise

    # -- incremental delta checkpoints (deltachain.py) -----------------------
    def enable_delta_capture(self) -> None:
        """Arm dirty-state tracking for delta commits (checkpointMode:
        "delta"). Call after construction or after a resume install — the
        capture baseline is the CURRENT state, which must equal the chain
        tail the next delta will append to."""
        self._delta_track = True
        # CPU backend: gather the epoch's cells/columns on a zero-copy
        # numpy view — no dispatch, no per-shape compile. Other backends
        # pay a device gather with tier-padded (bounded-compile) indices.
        self._delta_np_gather = jax.default_backend() == "cpu"
        self._delta_reset_capture()

    def _delta_gather(self, arr, index) -> np.ndarray:
        """One capture gather (``arr[index]``), returned as an owning numpy
        array in the npz schema's dtype (bf16 ring bits decode to exact
        f32). Advanced indexing copies on both paths, so the result never
        aliases a device buffer a later donated dispatch could invalidate."""
        if self._delta_np_gather:
            view = None
            try:
                view = cpu_zero_copy_view(arr)
            except Exception:
                pass  # exotic layout: fall through to the device gather
            if view is not None:
                out = view[index]
                if view.dtype == np.uint16:  # bf16 bit pattern -> exact f32
                    out = (out.astype(np.uint32) << 16).view(np.float32)
                return out
        out = arr[index]
        if out.dtype not in (jnp.float32, jnp.float64, jnp.int32):
            out = out.astype(jnp.float32)  # npz schema: no bf16
        return np.asarray(out)

    def _mark_cells(self, rows: np.ndarray, labels: np.ndarray) -> None:
        """Record the (row, bucket-slot) cells one ingest scatter touches."""
        nb = self.cfg.stats.num_buckets
        packed = rows.astype(np.int64) * nb + labels.astype(np.int64) % nb
        self._dirty_cells.update(np.unique(packed).tolist())

    # apm: sync-boundary: delta-capture baseline reads the ring cursors back once per epoch commit
    def _delta_reset_capture(self) -> None:
        self._dirty_cells.clear()
        self._delta_ticks = []
        self._delta_pos0 = [int(np.asarray(z.pos)) for z in self.state.zscores]
        self._delta_reg_base = self.registry.count

    # apm: sync-boundary: delta capture gathers the epoch's touched cells/columns back by contract (epoch cadence, not tick cadence)
    def _capture_delta(self, delivery_delta: Optional[dict] = None):
        """(arrays, meta) for one delta segment: everything the state changed
        since the last commit, at dirty-cell / pushed-column granularity.
        Does NOT reset tracking — the caller resets only after the segment
        is durably on disk, so a failed write retries with a superset."""
        cfg = self.cfg
        nb = cfg.stats.num_buckets
        ticks = list(self._delta_ticks)
        T = len(ticks)
        arrays: dict = {"latest_bucket": np.asarray(self.state.stats.latest_bucket)}
        meta: dict = {
            "capacity": int(cfg.capacity),
            "nb": int(nb),
            "ticks": ticks,
            "zchannels": [],
            "echannels": [],
        }
        if self._dirty_cells:
            packed = np.fromiter(self._dirty_cells, np.int64, len(self._dirty_cells))
            packed.sort()
            rows = (packed // nb).astype(np.int32)
            slots = (packed % nb).astype(np.int32)
            # pad the index vectors to power-of-two tiers: a shape-varying
            # gather would recompile per distinct cell count (the XLA trap
            # _ingest_arrays' pad tiers exist for). Padding REPEATS the
            # first cell — the duplicate scatters the same post-state value
            # twice at replay, which is idempotent by construction.
            rows = _pad_tier_repeat(rows)
            slots = _pad_tier_repeat(slots)
            arrays["cell_rows"] = rows
            arrays["cell_slots"] = slots
            st = self.state.stats
            # O(cells) gathers, not O(state) (zero-copy numpy view on CPU,
            # device gather elsewhere — _delta_gather)
            arrays["cell_counts"] = self._delta_gather(st.counts, (rows, slots))
            arrays["cell_sums"] = self._delta_gather(st.sums, (rows, slots))
            arrays["cell_nsamples"] = self._delta_gather(st.nsamples, (rows, slots))
            arrays["cell_samples"] = self._delta_gather(st.samples, (rows, slots))
        if T:
            for i, spec in enumerate(cfg.lags):
                z = self.state.zscores[i]
                L = spec.lag
                key = f"z{spec.lag}"
                pos0 = self._delta_pos0[i]
                meta["zchannels"].append({"key": key, "lag": L, "pos0": pos0})
                if T >= L:
                    # every ring slot was rewritten this epoch: store the
                    # whole ring (the full snapshot's representation)
                    zvals = np.asarray(
                        z.values.astype(jnp.float32)
                        if z.values.dtype not in (jnp.float32, jnp.float64)
                        else z.values
                    )
                    arrays[f"{key}_values"] = zvals
                else:
                    # tier-padded with the last position repeated (same
                    # column gathered twice == same column written twice at
                    # replay); apply_delta slices back to len(ticks)
                    positions = _pad_tier_repeat(
                        np.asarray([(pos0 + t) % L for t in range(T)], np.int32),
                        last=True,
                    )
                    arrays[f"{key}_push"] = self._delta_gather(
                        z.values, (slice(None), slice(None), positions)
                    )
                arrays[f"{key}_fill"] = np.asarray(z.fill)
                arrays[f"{key}_pos"] = np.asarray(z.pos)
                arrays[f"{key}_counters"] = np.asarray(self.state.alert_counters[i])
            buf1 = cfg.stats.buffer_sz + 1
            for i, espec in enumerate(cfg.ewma):
                e = self.state.ewmas[i]
                K = espec.season_slots
                ek = f"e{espec.channel_id}x{K}x{espec.slot_intervals}"
                slots_e = sorted(
                    {((nl - buf1) // espec.slot_intervals) % K for nl in ticks}
                )
                meta["echannels"].append({"key": ek, "slots": slots_e})
                if len(slots_e) >= K:
                    arrays[f"{ek}_mean"] = np.asarray(e.mean)
                    arrays[f"{ek}_var"] = np.asarray(e.var)
                    arrays[f"{ek}_trend"] = np.asarray(e.trend)
                    arrays[f"{ek}_count"] = np.asarray(e.count)
                else:
                    sl = _pad_tier_repeat(np.asarray(slots_e, np.int32), last=True)
                    ix3 = (slice(None), slice(None), sl)
                    arrays[f"{ek}_mean_cols"] = self._delta_gather(e.mean, ix3)
                    arrays[f"{ek}_var_cols"] = self._delta_gather(e.var, ix3)
                    arrays[f"{ek}_trend_cols"] = self._delta_gather(e.trend, ix3)
                    arrays[f"{ek}_count_cols"] = self._delta_gather(
                        e.count, (slice(None), sl)
                    )
                arrays[f"{ek}_counters"] = np.asarray(self.state.ewma_counters[i])
        new_keys = self.registry.rows()[self._delta_reg_base :]
        if new_keys:
            meta["registry_new"] = ["\x00".join(k) for k in new_keys]
        if T or self._dirty_cells:
            # any feed/tick may have moved the ordered-tx heap/backlog;
            # bounded by the window buffer (drained past the edge every tick)
            meta["pending"] = self._pending_tx_lines()
        if delivery_delta is not None:
            meta["delivery_delta"] = delivery_delta
        return arrays, meta

    @property
    def has_uncheckpointed_changes(self) -> bool:
        """True when delta-capture tracking has recorded engine changes
        since the last committed epoch (dirty cells, executed ticks,
        registry growth, or pending ordered-tx). False only under active
        tracking — with tracking off, idleness cannot be proven and the
        caller must not skip its checkpoint. Lets an idle worker's save
        cadence skip no-op commits instead of appending empty deltas
        (chains otherwise grow one segment per interval — and one per
        boot — while serving nothing)."""
        if not self._delta_track:
            return True
        # NOTE: heap/backlog content is deliberately NOT consulted — a
        # restored engine re-seeds its pending-tx buffer from the last
        # commit, and every path that grows it also dirties cells or
        # executes ticks, so the buffer alone never constitutes a change
        return bool(
            self._dirty_cells or self._delta_ticks
            or self.registry.count != self._delta_reg_base
        )

    def save_resume_delta(self, chain, *, delivery_delta: Optional[dict] = None) -> int:
        """Commit one epoch as a delta segment appended to ``chain``
        (deltachain.DeltaChain). The delta + the worker's incremental dedup
        record form the SAME atomic commit unit the full snapshot provides:
        a msg id is in the chain's recovered window iff its effect is in the
        chain's recovered tensors. Raises deltachain.CheckpointWriteError on
        storage failure — tracking is NOT reset, so the retry captures a
        superset and the chain still ends at a committed boundary."""
        if not self._delta_track:
            raise RuntimeError("delta capture not enabled (enable_delta_capture)")
        self.flush()  # pending scatters + held emission belong to this epoch
        with self._att_save.span():
            arrays, meta = self._capture_delta(delivery_delta)
            epoch = chain.append(arrays, meta)
        self._att_save.count("bytes", chain.last_delta_bytes)
        self._delta_reset_capture()
        return epoch

    def load_resume_chain(self, chain) -> bool:
        """Restore from a delta chain (deltachain.DeltaChain or directory
        path): base + ordered deltas replayed to the last committed epoch,
        then installed through the exact same path as a full-snapshot
        restore. Returns False (start fresh) when no readable chain exists."""
        from .deltachain import DeltaChain

        if isinstance(chain, str):
            chain = DeltaChain(chain, logger=self.logger)
        rec = chain.load()
        if rec is None or rec.data is None:
            return False
        self.drain_emission()  # pre-restore emissions belong to the old stream
        if not self._install_resume_data(rec.data, f"chain {chain.directory}"):
            return False
        if self._delta_track:
            self._delta_reset_capture()
        return True

    def load_resume(self, path: str) -> bool:
        if not os.path.exists(path):
            return False
        self.drain_emission()  # pre-restore emissions belong to the old stream
        # Fully materialize the snapshot before touching any state: np.load
        # succeeds on any readable zip, and member reads (KeyError, zlib
        # errors on truncation) raise lazily — a corrupt file must mean
        # "start fresh", never a crash or a half-mutated driver.
        try:
            with np.load(path, allow_pickle=True) as npz:
                data = {name: npz[name] for name in npz.files}
        except Exception:
            if self.logger:
                self.logger.error(f"Could not load resume snapshot (starting fresh): {path}")
            return False
        return self._install_resume_data(data, path)

    # apm: sync-boundary: resume install materializes host arrays onto the device once at boot
    def _install_resume_data(self, data: dict, source: str) -> bool:
        """Install a full-snapshot ``data`` dict (npz schema) into the live
        driver — shared by the npz path and the delta-chain replay, so a
        chain restore is bit-identical to restoring a full snapshot of the
        same state. Validation failure means "start fresh", never a crash."""
        try:
            keys = [tuple(k.split("\x00", 1)) for k in data["registry"].tolist()]
            required = ["latest_bucket", "counts", "sums", "samples", "nsamples"]
            for spec in self.cfg.lags:
                required += [f"z{spec.lag}_{f}" for f in ("values", "fill", "pos", "counters")]
            for espec in self.cfg.ewma:
                ek = f"e{espec.channel_id}x{espec.season_slots}x{espec.slot_intervals}"
                required += [f"{ek}_{f}" for f in ("mean", "var", "count", "counters")]
            missing = [name for name in required if name not in data]
            if missing:
                raise KeyError(missing[0])
        except Exception:
            if self.logger:
                self.logger.error(f"Could not load resume snapshot (starting fresh): {source}")
            return False
        needed = len(keys)
        while needed > self.cfg.capacity:
            self._grow()
        self.registry = ServiceRegistry(self.cfg.capacity)
        for server, service in keys:
            self.registry.lookup_or_add(server, service)
        # the registry was rebuilt: decoder-id -> row mappings are stale, and
        # re-resolving old interned keys eagerly would register absent
        # services early. Start a fresh decoder lazily instead.
        if self._native_dec is not None:
            self._native_dec.close()
        self._native_dec = None
        self._native_dec_tried = False
        self._reset_decode_map()

        def pad_rows(a: np.ndarray) -> np.ndarray:
            if a.shape and a.shape[0] < self.cfg.capacity:
                pad_width = [(0, self.cfg.capacity - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
                fill = np.nan if np.issubdtype(a.dtype, np.floating) else 0
                return np.pad(a, pad_width, constant_values=fill)
            return a[: self.cfg.capacity]

        stats_state = dstats.StatsState(
            latest_bucket=jnp.asarray(data["latest_bucket"]),
            counts=jnp.asarray(pad_rows(data["counts"])),
            sums=jnp.asarray(pad_rows(data["sums"])),
            samples=jnp.asarray(pad_rows(data["samples"])),
            nsamples=jnp.asarray(pad_rows(data["nsamples"])),
        )
        zstates, counters = [], []
        ring_dtype = self.cfg.zscore_ring_dtype or self.cfg.stats.dtype
        for spec in self.cfg.lags:
            values_np = pad_rows(data[f"z{spec.lag}_values"])
            fill_np = pad_rows(data[f"z{spec.lag}_fill"])
            pos_np = np.asarray(data[f"z{spec.lag}_pos"])
            if pos_np.ndim == 0:
                pos = jnp.asarray(pos_np, jnp.int32)
            else:
                # legacy snapshot with PER-ROW cursors: rotate each row so
                # its next-write slot lands on the shared cursor 0 (window
                # content and eviction order are rotation-invariant, so the
                # restored engine is bit-equivalent to the legacy layout)
                values_np = dzscore.normalize_legacy_ring(
                    values_np, fill_np, pad_rows(pos_np), spec.lag
                )
                pos = jnp.zeros((), jnp.int32)
            values = jnp.asarray(values_np).astype(ring_dtype)
            zstates.append(
                dzscore.ZScoreState(
                    values=values,
                    fill=jnp.asarray(fill_np),
                    pos=pos,
                )
            )
            counters.append(jnp.asarray(pad_rows(data[f"z{spec.lag}_counters"])))
        estates, ecounters = [], []
        for espec in self.cfg.ewma:
            ek = f"e{espec.channel_id}x{espec.season_slots}x{espec.slot_intervals}"
            mean = pad_rows(data[f"{ek}_mean"])
            # trend is absent in pre-Holt snapshots: zero-fill == the exact
            # plain-EWMA state those snapshots were saved under
            trend = (
                pad_rows(data[f"{ek}_trend"])
                if f"{ek}_trend" in data
                else np.zeros_like(mean)
            )
            estates.append(
                dewma.EwmaState(
                    mean=jnp.asarray(mean),
                    var=jnp.asarray(pad_rows(data[f"{ek}_var"])),
                    count=jnp.asarray(pad_rows(data[f"{ek}_count"])),
                    trend=jnp.asarray(trend),
                )
            )
            ecounters.append(jnp.asarray(pad_rows(data[f"{ek}_counters"])))
        # the sliding aggregates are DERIVED state: rebuilt exactly from the
        # restored rings, so snapshot schemas are unchanged and pre-sliding
        # snapshots restore 1:1 (shared derivation: engine_derive_aggs)
        self.state = engine_derive_aggs(
            EngineState(
                stats_state, tuple(zstates), tuple(counters), tuple(estates), tuple(ecounters)
            ),
            self.cfg,
        )
        self._latest_label = int(data["latest_bucket"])
        self.delivery_state = None
        if "delivery_state" in data:  # optional: absent for at-most-once
            import json as _json

            try:
                self.delivery_state = _json.loads(data["delivery_state"].item())
            except Exception:
                # a mangled delivery record must not reject the engine
                # snapshot: worst case the dedup window starts empty and a
                # redelivery double-counts — the at-most-once baseline
                if self.logger:
                    self.logger.error(
                        f"Resume snapshot delivery state unreadable (ignored): {source}"
                    )
        self.heap = MinHeap(lambda tx: tx.end_ts)
        self._tx_backlog = []
        if "pending_tx" in data:  # optional: absent in older snapshots
            from .entries import EntryFactory

            fac = EntryFactory()
            for line in data["pending_tx"].tolist():
                if self.on_ordered_tx is not None:
                    entry = fac.from_csv(line)
                    if entry is not None and entry.type == "tx":
                        self.heap.push(entry)
                elif self.on_ordered_csv is not None:
                    p = line.split("|")
                    if len(p) == 9 and p[0] == "tx":
                        try:
                            self._tx_backlog.append((float(p[6]), line))
                        except ValueError:
                            pass
        self._refresh_params()
        return True

    # -- partition row handoff (parallel/fleet.py, DESIGN.md §10) ------------
    # The quiesced-rebalance primitives: a partition's service rows leave one
    # engine and join another as npz-schema dicts, through the SAME install
    # path checkpoints restore through — so a handed-off row is bit-identical
    # to one that was checkpointed and restored. All three are epoch-cadence
    # operations (full capture + reinstall): rebalances are rare control-plane
    # events, and reusing the battle-tested snapshot path beats a bespoke
    # incremental row mover that would need its own bit-identity proofs.

    def _row_array_names(self, data: dict) -> List[str]:
        """Capture keys indexed by service row (first axis == capacity):
        stats planes, z rings/fill/counters, EWMA planes — everything except
        the 0-d cursors (latest_bucket, z pos) and object arrays (registry,
        pending_tx, delivery_state)."""
        return [
            k for k, a in data.items()
            if isinstance(a, np.ndarray) and a.dtype != np.dtype(object)
            and a.ndim >= 1 and a.shape[0] == self.cfg.capacity
        ]

    def export_service_rows(self, pred) -> dict:
        """Snapshot the rows whose ``(server, service)`` key satisfies
        ``pred`` as a self-contained npz-schema dict (cursors included, so
        the importer can re-align ring rotation), WITHOUT mutating this
        engine. Pending ordered-tx lines of those services ride along."""
        self.flush()
        self.drain_emission()
        data = self._capture_resume_arrays(None)
        keys = self.registry.rows()
        take = [i for i, (srv, svc) in enumerate(keys) if pred(srv, svc)]
        idx = np.asarray(take, np.intp)
        out = {k: np.array(data[k][idx]) for k in self._row_array_names(data)}
        out["latest_bucket"] = np.asarray(data["latest_bucket"])
        for spec in self.cfg.lags:
            out[f"z{spec.lag}_pos"] = np.asarray(data[f"z{spec.lag}_pos"])
        out["registry"] = np.array(
            ["\x00".join(keys[i]) for i in take], dtype=object
        )
        out["pending_tx"] = np.array(
            [ln for ln in data["pending_tx"].tolist()
             if self._pending_line_matches(ln, pred)],
            dtype=object,
        )
        return out

    @staticmethod
    def _pending_line_matches(line: str, pred) -> bool:
        p = line.split("|", 3)
        return len(p) >= 3 and pred(p[1], p[2])

    def remove_service_rows(self, pred) -> int:
        """Drop the rows whose key satisfies ``pred`` (the release half of a
        handoff): the remaining rows are re-installed through the resume
        path, so row indices compact and derived aggregates rebuild exactly
        as a restore would. Returns the number of rows removed."""
        self.flush()
        self.drain_emission()
        data = self._capture_resume_arrays(None)
        keys = self.registry.rows()
        keep = [i for i, (srv, svc) in enumerate(keys) if not pred(srv, svc)]
        removed = len(keys) - len(keep)
        if removed == 0:
            return 0
        idx = np.asarray(keep, np.intp)
        for k in self._row_array_names(data):
            data[k] = np.array(data[k][idx])
        data["registry"] = np.array(
            ["\x00".join(keys[i]) for i in keep], dtype=object
        )
        data["pending_tx"] = np.array(
            [ln for ln in data["pending_tx"].tolist()
             if not self._pending_line_matches(ln, pred)],
            dtype=object,
        )
        if not self._install_resume_data(data, "partition-release"):
            raise RuntimeError("row removal re-install failed")
        if self._delta_track:
            self._delta_reset_capture()
        return removed

    def import_service_rows(self, incoming: dict) -> int:
        """Merge an :meth:`export_service_rows` dict into this engine (the
        adopt half of a handoff). Incoming z-ring columns are rotated from
        the exporter's shared cursor/label onto this engine's, so a row's
        window reads the same label sequence it would have on its old owner;
        stats/EWMA planes are label-slot indexed and merge as-is, with cells
        older than the merged bucket window cleared. Duplicate service keys
        are a routing-discipline violation and raise (one partition, one
        owner — shardmodel owner-locality)."""
        self.flush()
        self.drain_emission()
        in_keys = [tuple(k.split("\x00", 1))
                   for k in incoming["registry"].tolist()]
        if not in_keys:
            return 0
        cur = self._capture_resume_arrays(None)
        cur_keys = self.registry.rows()
        dup = set(cur_keys) & set(in_keys)
        if dup:
            raise ValueError(
                f"import_service_rows: {len(dup)} keys already live here "
                f"(first: {sorted(dup)[0]}) — a partition can only have one "
                f"owner"
            )
        cur_label = int(cur["latest_bucket"])
        in_label = int(incoming["latest_bucket"])
        new_label = max(cur_label, in_label)
        nb = self.cfg.stats.num_buckets
        n_cur = len(cur_keys)
        merged: dict = {}
        for k in self._row_array_names(cur):
            inc = np.array(incoming[k])
            merged[k] = np.concatenate([np.array(cur[k][:n_cur]), inc], axis=0)
        # ring rotation: column of label t sits at (pos - 1 - (label - t))
        # mod L, so aligning the two histories shifts incoming columns by
        # (cur_pos - in_pos - (cur_label - in_label)) mod L
        for spec in self.cfg.lags:
            L = spec.lag
            cur_pos = int(np.asarray(cur[f"z{L}_pos"]))
            in_pos = int(np.asarray(incoming[f"z{L}_pos"]))
            d = (cur_pos - in_pos - (cur_label - in_label)) % L
            if d:
                vk = f"z{L}_values"
                merged[vk][n_cur:] = np.roll(
                    np.array(incoming[vk]), d, axis=-1
                )
        # bucket-slot hygiene across a label skew: slot s last held label
        # latest - ((latest - s) % nb); anything at or below new_label - nb
        # is outside the merged window and must read empty (the live engine
        # clears those slots as it advances — a handoff must not resurrect
        # them)
        if in_label != cur_label:
            slots = np.arange(nb)
            for label0, rows in ((in_label, slice(n_cur, None)),
                                 (cur_label, slice(0, n_cur))):
                dead = (label0 - ((label0 - slots) % nb)) <= new_label - nb
                if not dead.any():
                    continue
                for k in ("counts", "sums", "nsamples", "samples"):
                    merged[k][rows, dead] = 0
        # keep the cursor dtype of the capture (int32): a bare python int
        # would become int64 and poison every label-indexed dynamic slice
        # under x64
        merged["latest_bucket"] = np.asarray(
            new_label, dtype=np.asarray(cur["latest_bucket"]).dtype
        )
        for spec in self.cfg.lags:
            merged[f"z{spec.lag}_pos"] = np.asarray(cur[f"z{spec.lag}_pos"])
        merged["registry"] = np.array(
            ["\x00".join(k) for k in list(cur_keys) + in_keys], dtype=object
        )
        merged["pending_tx"] = np.array(
            cur["pending_tx"].tolist() + incoming["pending_tx"].tolist(),
            dtype=object,
        )
        if "delivery_state" in cur:
            merged["delivery_state"] = cur["delivery_state"]
        if not self._install_resume_data(merged, "partition-adopt"):
            raise RuntimeError("row import re-install failed")
        if self._delta_track:
            self._delta_reset_capture()
        return len(in_keys)
