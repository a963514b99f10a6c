"""Sharded engine step: shard_map over the service axis + ICI rollups.

Each shard runs the full fused tick (stats -> quantize -> zscore -> alerts) on
its local row block — zero cross-shard traffic, since per-key state is
independent (SURVEY.md §2.5 point 3) — and contributes to fleet-level rollup
baselines via ``jax.lax.psum`` over the ``services`` axis: the ICI all-reduce
of BASELINE.json's north star. The rollup is the pod-scale replacement for the
reference's single-process global view (queue-depth/throughput logging and
fleet dashboards, SURVEY.md §5.5):

- total window tx count + global mean elapsed across every service
- fleet signal counts per direction (how many services are anomalous NOW)
- alert-trigger counts per lag

Ingest is also shard_mapped: the host routes each record to the shard that
owns its row (rows block-partitioned: shard = row // rows_per_shard), so the
scatter stays shard-local — on a multi-host pod this is the DCN host-batch
scatter, on one host it is just a reshape.
"""

from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..ops.ewma import EwmaState
from ..ops.stats import StatsState
from ..ops.zscore import SlidingAgg, ZScoreState
from ..ops import zscore as dzscore
from ..ops import stats as dstats
from ..pipeline import (
    EngineConfig,
    EngineParams,
    EngineState,
    LagEmission,
    TickEmission,
    _StaggeredRebuildBase,
    _rebuild_rotation,
    _staged_ring_update,
    cpu_zero_copy_view,
    default_native_rebuild_gate,
    engine_core_tick,
    engine_ingest,
    engine_needs_rebuild,
    engine_rebuild_aggs,
    engine_rebuild_slice,
    engine_tick,
    sliding_lag_indices,
    zscore_cfg,
)
from .mesh import SERVICE_AXIS


class FleetRollup(NamedTuple):
    """Pod-wide aggregates, psum'd over ICI; replicated on every shard."""

    total_tx: jnp.ndarray  # scalar int: window tx count across the fleet
    mean_elapsed: jnp.ndarray  # scalar: global mean of per-service averages
    signals_high: jnp.ndarray  # [n_lags + n_ewma] int: services signalling +1 (avg metric)
    signals_low: jnp.ndarray  # [n_lags + n_ewma] int: services signalling -1
    alerts: jnp.ndarray  # [n_lags + n_ewma] int: alert triggers this tick


def _fleet_rollup(emission: TickEmission) -> FleetRollup:
    """ICI all-reduce of the shard-local emission into the pod-wide view —
    the one place the per-tick collectives live (shared by the mono and
    staged sharded executors so the rollup semantics cannot drift)."""
    total_tx = jax.lax.psum(jnp.sum(emission.count), SERVICE_AXIS)
    avg = emission.average[:, 0]
    defined = ~jnp.isnan(avg)
    s = jax.lax.psum(jnp.sum(jnp.where(defined, avg, 0)), SERVICE_AXIS)
    n = jax.lax.psum(jnp.sum(defined), SERVICE_AXIS)
    mean_elapsed = jnp.where(n > 0, s / jnp.maximum(n, 1), jnp.nan)
    # lag windows first, then EWMA/seasonal channels (axis order matches
    # cfg.lags + cfg.ewma)
    chans = list(emission.lags) + list(emission.ewma)
    sig_hi = jnp.stack(
        [jax.lax.psum(jnp.sum(l.signal[:, 0] == 1), SERVICE_AXIS) for l in chans]
    )
    sig_lo = jnp.stack(
        [jax.lax.psum(jnp.sum(l.signal[:, 0] == -1), SERVICE_AXIS) for l in chans]
    )
    alerts = jnp.stack(
        [jax.lax.psum(jnp.sum(l.trigger), SERVICE_AXIS) for l in chans]
    )
    return FleetRollup(total_tx, mean_elapsed, sig_hi, sig_lo, alerts)


def _local_tick_with_rollup(cfg: EngineConfig):
    def fn(state: EngineState, new_label, params: EngineParams):
        emission, new_state = engine_tick(state, cfg, new_label, params)
        return emission, _fleet_rollup(emission), new_state

    return fn


def _local_core_with_rollup(cfg: EngineConfig):
    from ..pipeline import engine_core_tick

    def fn(state: EngineState, new_label, params: EngineParams, evicted):
        emission, new_state, pushes = engine_core_tick(
            state, cfg, new_label, params, evicted
        )
        return emission, _fleet_rollup(emission), new_state, pushes

    return fn


_ROW = P(SERVICE_AXIS)


def _local_rows_contiguous(mesh: Mesh) -> bool:
    """True when this process's devices own one CONTIGUOUS run of the
    service-axis row space — the layout the per-addressable-shard native
    stages assume when they hand ``jax.make_array_from_process_local_data``
    a row-ordered concatenation of local blocks. Always true single-process;
    true on standard multi-host meshes (each host's devices are consecutive
    in ``jax.devices()`` order); a deliberately permuted mesh falls back to
    the fused in-program paths instead of producing misplaced rows."""
    if jax.process_count() == 1:
        return True
    me = jax.process_index()
    pos = [i for i, d in enumerate(mesh.devices.flat) if d.process_index == me]
    return bool(pos) and pos[-1] - pos[0] + 1 == len(pos)


def _zstate_spec(cfg: EngineConfig, spec) -> ZScoreState:
    # sliding aggregates are all per-row ([S, 3]); the pytree spec must
    # mirror what zscore.init_state builds for this lag or shard_map rejects
    # the state
    agg = (
        SlidingAgg(
            cnt=_ROW, vsum=_ROW, vsumsq=_ROW, anchor=_ROW,
            run_len=_ROW, last_valid=_ROW, last_push=_ROW,
        )
        if zscore_cfg(cfg, spec).sliding_active
        else None
    )
    return ZScoreState(values=_ROW, fill=_ROW, pos=P(), agg=agg)  # pos: global scalar


def _state_specs(cfg: EngineConfig) -> EngineState:
    return EngineState(
        stats=StatsState(latest_bucket=P(), counts=_ROW, sums=_ROW, samples=_ROW, nsamples=_ROW),
        zscores=tuple(_zstate_spec(cfg, spec) for spec in cfg.lags),
        alert_counters=tuple(_ROW for _ in cfg.lags),
        ewmas=tuple(
            EwmaState(mean=_ROW, var=_ROW, count=_ROW, trend=_ROW) for _ in cfg.ewma
        ),
        ewma_counters=tuple(_ROW for _ in cfg.ewma),
    )


def _params_specs(cfg: EngineConfig) -> EngineParams:
    return EngineParams(
        thresholds=tuple(_ROW for _ in cfg.lags),
        influences=tuple(_ROW for _ in cfg.lags),
        hard_max_ms=_ROW,
        suppressed=_ROW,
        active=_ROW,
        ewma_thresholds=tuple(_ROW for _ in cfg.ewma),
        ewma_influences=tuple(_ROW for _ in cfg.ewma),
    )


def _emission_specs(cfg: EngineConfig) -> TickEmission:
    lag_spec = LagEmission(
        window_avg=_ROW, lower_bound=_ROW, upper_bound=_ROW, signal=_ROW,
        trigger=_ROW, cause_bits=_ROW,
    )
    return TickEmission(
        tpm=_ROW, average=_ROW, count=_ROW, overflowed=_ROW,
        lags=tuple(lag_spec for _ in cfg.lags),
        ewma=tuple(lag_spec for _ in cfg.ewma),
    )


def local_config(cfg: EngineConfig, n_shards: int) -> EngineConfig:
    if cfg.capacity % n_shards != 0:
        raise ValueError(f"capacity {cfg.capacity} not divisible by mesh size {n_shards}")
    return cfg._replace(stats=cfg.stats._replace(capacity=cfg.capacity // n_shards))


def make_sharded_tick(mesh: Mesh, cfg: EngineConfig):
    """jit(shard_map(tick + ICI rollup)) over the service-axis mesh."""
    n = mesh.devices.size
    fn = _local_tick_with_rollup(local_config(cfg, n))
    mapped = shard_map(
        fn,
        mesh=mesh,
        in_specs=(_state_specs(cfg), P(), _params_specs(cfg)),
        out_specs=(_emission_specs(cfg), FleetRollup(P(), P(), P(), P(), P()), _state_specs(cfg)),
    )
    # donate the state: without it every tick copies the [S, NB, CAP] sample
    # buffers (the dominant HBM traffic); callers always rebind state
    return jax.jit(mapped, donate_argnums=(0,))


def make_sharded_step(mesh: Mesh, cfg: EngineConfig):
    """The STAGED pod-scale executor: ``step(state, new_label, params) ->
    (emission, rollup, new_state)`` — the sharded counterpart of
    pipeline.make_engine_step, with the same read-free-writer staging so the
    big per-shard buffers are never copied (XLA:CPU copy hazard; on TPU the
    staged layout is likewise the guaranteed in-place shape):

      1. stats advance-one DUS per new label (plain jit — the slice update
         touches the UNsharded bucket axis, so SPMD partitioning handles the
         row-sharded arrays without collectives or shard_map),
      2. z-ring evict slices (plain jit, read-only, same SPMD argument),
      3. the shard_mapped ring-free core with the ICI fleet rollup — the
         only program with collectives,
      4. pure-DUS ring writes (plain jit, donated).

    On the CPU backend (single process, percentileImpl auto/native, f32,
    toolchain present) the percentile stage moves to the HOST exactly like
    the single-chip executor, but per addressable shard: each device's
    sample-reservoir block is viewed zero-copy and handed to the native
    nth_element kernel — on a real pod each HOST would select only its own
    shards' percentiles, so the reservoir never crosses a host boundary.
    Overflow ticks fall back to the in-program jitted paths.
    """
    from ..pipeline import make_staged_executor

    n = mesh.devices.size
    lcfg = local_config(cfg, n)
    espec = tuple(_ROW for _ in sliding_lag_indices(cfg))

    # EXPLICIT fused mode (tpuEngine.tickExecutor="fused" / APM_TICK_EXECUTOR):
    # the whole staged choreography collapses into ONE shard_mapped donated
    # dispatch per tick, with the staggered-rebuild chunk folded in
    # (rebuild_integrated — callers skip ShardedRebuildScheduler). "auto"
    # deliberately resolves to STAGED here regardless of size: pod shapes
    # are the staged executor's home turf (per-shard rings are huge, and the
    # staged native percentile/rebuild kernels are the CPU-fallback wins),
    # and the two-process agreement tests keep exercising that path.
    want_fused = (os.environ.get("APM_TICK_EXECUTOR") or cfg.tick_executor) == "fused"
    if jax.process_count() > 1:
        # executor choice is part of the dispatch sequence: divergence
        # (e.g. one host's env override) would deadlock the collectives,
        # so agree pod-globally — fused only if EVERY host wants it
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.array([1 if want_fused else 0], np.int32)
        )
        agreed_fused = bool(np.min(flags))
        if want_fused and not agreed_fused:
            import logging

            logging.getLogger(__name__).warning(
                "fused sharded executor disabled POD-WIDE: %d of %d hosts "
                "did not request it; all hosts run the staged executor",
                int(len(flags) - np.sum(flags)), len(flags),
            )
        want_fused = agreed_fused
    if want_fused:
        return _make_fused_sharded_step(mesh, cfg, lcfg)

    def _make_core(local_fn, extra_in=(), extra_out=()):
        return jax.jit(
            shard_map(
                local_fn,
                mesh=mesh,
                in_specs=(_state_specs(cfg), P(), _params_specs(cfg), espec) + extra_in,
                out_specs=(
                    _emission_specs(cfg),
                    FleetRollup(P(), P(), P(), P(), P()),
                    _state_specs(cfg),
                    espec,
                ) + extra_out,
            ),
            donate_argnums=(0,),
        )

    use_native = False
    if (
        cfg.stats.percentile_impl in ("auto", "native")
        and cfg.stats.dtype != jnp.float64
        and jax.default_backend() == "cpu"
        and _local_rows_contiguous(mesh)
        # test hook simulating a host whose toolchain build failed — the
        # agreement collective below must then force EVERY host fused
        # (explicit "1": a stray "0" must not silently disable the stage)
        and os.environ.get("APM_DISABLE_NATIVE_PCT") != "1"
    ):
        from .. import native as _native

        use_native = _native.have_native_percentiles()
    if jax.process_count() > 1:
        # the executor CHOICE must be pod-global: toolchain availability and
        # row-contiguity are host-local facts, and hosts running different
        # executors dispatch different program sequences => the first staged
        # tick deadlocks in the collectives. Every host reaches this
        # allgather (unconditionally), then all take native only if ALL can.
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.array([1 if use_native else 0], np.int32)
        )
        agreed = bool(np.min(flags))
        if use_native and not agreed:
            # never silently: the native stage is the ~3x CPU percentile win
            import logging

            logging.getLogger(__name__).warning(
                "native percentile stage disabled POD-WIDE: %d of %d hosts "
                "lack it (toolchain/contiguity); all hosts take the fused "
                "in-program path to keep dispatch sequences identical",
                int(len(flags) - np.sum(flags)), len(flags),
            )
        use_native = agreed

    if not use_native:
        core = _make_core(_local_core_with_rollup(lcfg))
        # the staging choreography itself (advance clamp, evict/write slot
        # math, donation order) is pipeline.make_staged_executor — ONE
        # implementation for the single-chip and pod executors
        return make_staged_executor(
            cfg,
            core=lambda state, nl, params, evicted: core(
                state, jnp.int32(nl), params, evicted
            ),
        )

    from ..native import window_percentiles_native
    from ..ops import stats as dstats_mod
    from ..pipeline import engine_core_tick_stats

    # panel stats per shard (no collectives: per-row quantities)
    pre = jax.jit(
        shard_map(
            lambda st: dstats_mod.window_pre(st, lcfg.stats),
            mesh=mesh,
            in_specs=(_state_specs(cfg).stats,),
            out_specs=dstats_mod.TickResult(_ROW, _ROW, _ROW, _ROW, _ROW, _ROW),
        )
    )
    weighted_lcfg = lcfg.stats._replace(percentile_impl="sort")
    weighted = jax.jit(
        shard_map(
            lambda st: dstats_mod.window_stats(st, weighted_lcfg),
            mesh=mesh,
            in_specs=(_state_specs(cfg).stats,),
            out_specs=dstats_mod.TickResult(_ROW, _ROW, _ROW, _ROW, _ROW, _ROW),
        )
    )
    res_spec = dstats_mod.TickResult(_ROW, _ROW, _ROW, _ROW, _ROW, _ROW)

    def _core_stats(state, new_label, params, evicted, stats_res):
        emission, new_state, pushes = engine_core_tick_stats(
            state, lcfg, new_label, params, evicted, stats_res
        )
        return emission, _fleet_rollup(emission), new_state, pushes

    core = _make_core(_core_stats, extra_in=(res_spec,))
    NB = cfg.stats.num_buckets
    offsets = np.arange(cfg.stats.buffer_sz, cfg.stats.num_keep + 1)
    pct_sharding = jax.sharding.NamedSharding(mesh, _ROW)
    multi_host = jax.process_count() > 1
    # the native-vs-weighted branch must be the SAME decision on every host
    # (divergence would dispatch different programs => distributed deadlock):
    # a replicated jitted any() reduces the sharded overflow flags over ICI
    # and every host reads the same scalar
    any_overflow = jax.jit(
        jnp.any, out_shardings=jax.sharding.NamedSharding(mesh, P())
    )

    # apm: sync-boundary: pod executor's host percentile stage — same sanctioned readback as the single-chip staged path
    def native_core(state, nl, params, evicted):
        res = pre(state.stats)
        if bool(jax.device_get(any_overflow(res.overflowed))):
            res = weighted(state.stats)
        else:
            latest = int(state.stats.latest_bucket)
            mask = np.zeros(NB, bool)
            mask[(latest - offsets) % NB] = True
            # per addressable shard: zero-copy view of the local reservoir
            # block, kernel per block — each HOST selects only its own
            # shards' percentiles; the reservoir never crosses a host
            # boundary (shards arrive row-ordered; _local_rows_contiguous
            # guaranteed the concatenation is this host's global row run)
            by_row = lambda s: s.index[0].start or 0
            shards = sorted(state.stats.samples.addressable_shards, key=by_row)
            cnt_shards = sorted(state.stats.nsamples.addressable_shards, key=by_row)
            blocks = []
            for sh, csh in zip(shards, cnt_shards):
                try:
                    block = np.from_dlpack(sh.data)
                    cblock = np.from_dlpack(csh.data)
                except Exception:  # pragma: no cover - dlpack unavailable
                    block = np.asarray(sh.data)
                    cblock = np.asarray(csh.data)
                # prefix-bounded gather (pipeline.make_engine_step note)
                blocks.append(window_percentiles_native(block, mask, (75, 95), cblock))
            pct = np.concatenate(blocks, axis=0)  # f32 — the gate excludes f64
            if multi_host:
                per75 = jax.make_array_from_process_local_data(
                    pct_sharding, np.ascontiguousarray(pct[:, 0])
                )
                per95 = jax.make_array_from_process_local_data(
                    pct_sharding, np.ascontiguousarray(pct[:, 1])
                )
            else:
                per75 = jax.device_put(np.ascontiguousarray(pct[:, 0]), pct_sharding)
                per95 = jax.device_put(np.ascontiguousarray(pct[:, 1]), pct_sharding)
            res = res._replace(per75=per75, per95=per95)
            native_core.native_pct_ticks += 1
        return core(state, jnp.int32(nl), params, evicted, res)

    native_core.native_pct_ticks = 0
    step = make_staged_executor(cfg, core=native_core)
    step.native_pct = native_core  # test/telemetry hook: .native_pct_ticks
    return step


def _make_fused_sharded_step(mesh: Mesh, cfg: EngineConfig, lcfg: EngineConfig):
    """The FUSED pod executor: one shard_mapped donated dispatch per tick
    (``fused_sharded_program``), with the staggered-rebuild chunk offset
    rotating on the host. Signature matches make_sharded_step:
    ``step(state, new_label, params) -> (emission, rollup, new_state)``;
    ``step.rebuild_integrated`` is True."""
    rebuild = engine_needs_rebuild(cfg)
    chunk, starts = _rebuild_rotation(lcfg) if rebuild else (0, [0])
    rot = {"i": 0}
    jfused = fused_sharded_program(mesh, cfg, lcfg)

    def step(state, new_label, params):
        s = starts[rot["i"]]
        rot["i"] = (rot["i"] + 1) % len(starts)
        return jfused(state, np.int32(new_label), params, np.int32(s))

    step.rebuild_integrated = rebuild
    step.kind = "fused"
    step.rebuild_rot = rot
    step.rebuild_chunk = chunk
    step.rebuild_starts = starts
    return step


def fused_sharded_program(mesh: Mesh, cfg: EngineConfig, lcfg: EngineConfig):
    """``jit(shard_map(...))`` of one fused pod tick — advance_span ->
    staggered-rebuild chunk -> ring-free core + ICI rollup -> in-place ring
    writes, the sharded counterpart of pipeline.make_fused_step's fused-all
    form: ``(state, label, params, rebuild_start) -> (emission, rollup,
    state)``, state donated. The rebuild chunk offset is shard-local (all
    shards rotate in lockstep through their row blocks, same schedule as
    ShardedRebuildScheduler) and runs BEFORE the tick so the chunk pass only
    ever reads the ring (the XLA:CPU read+write copy hazard)."""
    sliding_idx = sliding_lag_indices(cfg)
    rebuild = engine_needs_rebuild(cfg)
    chunk = _rebuild_rotation(lcfg)[0] if rebuild else 0

    def local_fn(state, nl, params, rb_start):
        state = state._replace(stats=dstats.advance_span(state.stats, lcfg.stats, nl))
        if rebuild:
            state = engine_rebuild_slice(state, lcfg, rb_start, chunk)
        rings = tuple(state.zscores[i].values for i in sliding_idx)
        cursors = tuple(state.zscores[i].pos for i in sliding_idx)
        evicted = tuple(
            dzscore.ring_evict_read(r, g) for r, g in zip(rings, cursors)
        )
        emission, state2, pushes = engine_core_tick(state, lcfg, nl, params, evicted)
        state2 = _staged_ring_update(lcfg, state2, pushes)
        return emission, _fleet_rollup(emission), state2

    mapped = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(_state_specs(cfg), P(), _params_specs(cfg), P()),
        out_specs=(
            _emission_specs(cfg),
            FleetRollup(P(), P(), P(), P(), P()),
            _state_specs(cfg),
        ),
        # advance_span's dynamic-trip loop has no replication rule; the
        # outputs' specs above are authoritative (rollup scalars really are
        # replicated by the psums)
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,))


def make_sharded_rebuild(mesh: Mesh, cfg: EngineConfig):
    """jit(shard_map(engine_rebuild_aggs)): the pod-scale counterpart of the
    host-counted periodic exact rebuild of the sliding z-score aggregates
    (pipeline.engine_rebuild_aggs — drift cancellation + anchor refresh).
    Every sharded tick loop owes a call each cfg.zscore_rebuild_every ticks,
    exactly like PipelineDriver's single-chip loop. Purely shard-local (the
    aggregates are per-row), so no collectives ride the rebuild."""
    n = mesh.devices.size
    lcfg = local_config(cfg, n)

    mapped = shard_map(
        lambda state: engine_rebuild_aggs(state, lcfg),
        mesh=mesh,
        in_specs=(_state_specs(cfg),),
        out_specs=_state_specs(cfg),
    )
    return jax.jit(mapped, donate_argnums=(0,))


class ShardedRebuildScheduler(_StaggeredRebuildBase):
    """Pod-scale counterpart of pipeline.RebuildScheduler: the staggered
    sliding-aggregate rebuild over the service-axis mesh.

    ``step(state)`` runs once per sharded tick and rebuilds ONE contiguous
    row chunk on EVERY shard simultaneously (the chunk offset is
    shard-local, so all shards rotate in lockstep through their own row
    blocks); a full rotation spans ``cfg.zscore_rebuild_every`` ticks, same
    drift bound as the monolithic make_sharded_rebuild pass with
    no tick ever stalling on a whole-ring reduction. Purely shard-local —
    the aggregates are per-row, so no collectives ride the rebuild.

    Backend-adaptive like make_sharded_step's percentile stage: on the
    single-process CPU backend with the toolchain present, each addressable
    shard's ring block is viewed zero-copy (bf16 rings via their uint16 bit
    pattern) and handed to the native streaming kernel
    (native/rebuild.cpp); only the [n_shards, chunk, 3] partials enter the
    jitted shard_mapped merge (ops/zscore.py merge_agg_slice — the same
    merge the single-chip scheduler and the XLA producer use). On a real
    pod each HOST would produce partials for its own shards only; the
    current gate mirrors the percentile stage's (single-process), with the
    jitted slice path serving every other topology (on TPU the per-shard
    [chunk, 3, L] fused reduce is microseconds).
    """

    def __init__(self, mesh: Mesh, cfg: EngineConfig, *, allow_native=None):
        self.cfg = cfg
        self.mesh = mesh
        self.active = engine_needs_rebuild(cfg)
        if not self.active:
            return
        n = mesh.devices.size
        lcfg = local_config(cfg, n)
        self._lcfg = lcfg
        S_l = lcfg.capacity
        self.chunk = dzscore.rebuild_chunk_rows(S_l, cfg.zscore_rebuild_every)
        self.n_chunks = -(-S_l // self.chunk)
        self.starts = [min(i * self.chunk, S_l - self.chunk) for i in range(self.n_chunks)]
        self._i = 0
        self._sliding_idx = sliding_lag_indices(cfg)
        chunk = self.chunk
        self._slice_fn = jax.jit(
            shard_map(
                lambda state, start: engine_rebuild_slice(state, lcfg, start, chunk),
                mesh=mesh,
                in_specs=(_state_specs(cfg), P()),
                out_specs=_state_specs(cfg),
            ),
            donate_argnums=(0,),
        )
        if allow_native is None:
            allow_native = default_native_rebuild_gate(cfg)
        self._native = False
        if allow_native:
            from .. import native as _native

            self._native = _native.have_native_rebuild()
        if self._native:
            agg_spec = SlidingAgg(
                cnt=_ROW, vsum=_ROW, vsumsq=_ROW, anchor=_ROW,
                run_len=_ROW, last_valid=_ROW, last_push=_ROW,
            )
            # partials travel as TWO dense arrays for the whole tick —
            # cnt [n_lags, n_shards, chunk, 3] i32 and the six float
            # planes [n_lags, 6, n_shards, chunk, 3] — so each tick costs
            # exactly two device_puts and ONE merge-program dispatch
            # (16 kernel calls + 14 puts + 2 dispatches measured 19 ms/tick
            # of pure overhead at the podshard shape before batching)
            self._cnt_sharding = jax.sharding.NamedSharding(mesh, P(None, SERVICE_AXIS))
            self._flt_sharding = jax.sharding.NamedSharding(
                mesh, P(None, None, SERVICE_AXIS)
            )
            sliding_idx = self._sliding_idx
            zcs = {i: zscore_cfg(lcfg, lcfg.lags[i]) for i in sliding_idx}

            def m(aggs, start, cntp, fltp):
                out = []
                for k, i in enumerate(sliding_idx):
                    c = cntp[k, 0]  # [chunk, 3] (shard axis dropped)
                    f = fltp[k, :, 0]  # [6, chunk, 3]
                    out.append(
                        dzscore.merge_agg_slice(
                            aggs[k], zcs[i], start,
                            c, f[0], f[1], f[2], f[3], f[4], f[5],
                        )
                    )
                return tuple(out)

            self._merge_fn = jax.jit(
                shard_map(
                    m,
                    mesh=mesh,
                    in_specs=(
                        tuple(agg_spec for _ in sliding_idx),
                        P(),
                        P(None, SERVICE_AXIS),
                        P(None, None, SERVICE_AXIS),
                    ),
                    out_specs=tuple(agg_spec for _ in sliding_idx),
                )
            )

    def _slice_call(self, state: EngineState, start: int) -> EngineState:
        return self._slice_fn(state, jnp.int32(start))

    # apm: sync-boundary: sharded rebuild's native window-agg pass reads the ring chunk back for the C++ kernel
    def _native_step(self, state: EngineState, start: int) -> EngineState:
        from .. import native as _native

        zs = list(state.zscores)
        end = start + self.chunk
        idx = self._sliding_idx
        n_shards = self.mesh.devices.size
        cntp = np.empty((len(idx), n_shards, self.chunk, 3), np.int32)
        fltp = np.empty((len(idx), 6, n_shards, self.chunk, 3), np.float32)
        by_row = lambda s: s.index[0].start or 0
        for k, i in enumerate(idx):
            z = zs[i]
            agg = z.agg
            ring_shards = sorted(z.values.addressable_shards, key=by_row)
            cnt_shards = sorted(agg.cnt.addressable_shards, key=by_row)
            vsum_shards = sorted(agg.vsum.addressable_shards, key=by_row)
            anc_shards = sorted(agg.anchor.addressable_shards, key=by_row)
            L = z.values.shape[-1]
            last_slot = (int(z.pos) - 1) % L
            for d, (rs, cs, vs, ans) in enumerate(
                zip(ring_shards, cnt_shards, vsum_shards, anc_shards)
            ):
                ring = cpu_zero_copy_view(rs.data)
                cnt = np.from_dlpack(cs.data)[start:end]
                vsum = np.from_dlpack(vs.data)[start:end]
                anc = np.from_dlpack(ans.data)[start:end]
                anchor_est = np.where(
                    cnt > 0, anc + vsum / np.maximum(cnt, 1).astype(np.float32), anc
                ).astype(np.float32)
                c, vsm, vs2, mn, mx, lastp = _native.window_aggs_native(
                    ring[start:end], anchor_est, last_slot
                )
                cntp[k, d] = c
                fltp[k, 0, d] = vsm
                fltp[k, 1, d] = vs2
                fltp[k, 2, d] = anchor_est
                fltp[k, 3, d] = mn
                fltp[k, 4, d] = mx
                fltp[k, 5, d] = lastp
        merged = self._merge_fn(
            tuple(zs[i].agg for i in idx),
            jnp.int32(start),
            jax.device_put(cntp, self._cnt_sharding),
            jax.device_put(fltp, self._flt_sharding),
        )
        for k, i in enumerate(idx):
            zs[i] = zs[i]._replace(agg=merged[k])
        return state._replace(zscores=tuple(zs))


def make_sharded_ingest(mesh: Mesh, cfg: EngineConfig):
    """jit(shard_map(ingest)): batches arrive pre-routed as
    [n_shards, B_local] arrays with shard-local row indices."""
    n = mesh.devices.size
    lcfg = local_config(cfg, n)

    def fn(state: EngineState, rows, labels, elapsed, valid):
        return engine_ingest(state, lcfg, rows[0], labels[0], elapsed[0], valid[0])

    batch_spec = P(SERVICE_AXIS)  # leading axis = shard
    mapped = shard_map(
        fn,
        mesh=mesh,
        in_specs=(_state_specs(cfg), batch_spec, batch_spec, batch_spec, batch_spec),
        out_specs=_state_specs(cfg),
    )
    return jax.jit(mapped, donate_argnums=(0,))


def route_batch(rows, labels, elapsed, valid, *, capacity: int, n_shards: int, batch_per_shard: int):
    """Host-side: route a global batch into per-shard slots with local row ids.

    Returns [n_shards, batch_per_shard] arrays (the DCN scatter layout)."""
    rows = np.asarray(rows)
    if capacity % n_shards != 0:
        raise ValueError(
            f"capacity {capacity} is not divisible by n_shards {n_shards}; "
            f"pad to {((capacity + n_shards - 1) // n_shards) * n_shards} "
            f"(see mesh.padded_capacity)"
        )
    labels = np.asarray(labels)
    elapsed = np.asarray(elapsed)
    valid = np.asarray(valid, bool)
    rows_per_shard = capacity // n_shards

    # Vectorized placement (no per-record Python): compact the valid entries,
    # stable-sort by owning shard (stable => arrival order preserved within a
    # shard), then each record's slot is its rank within its shard group.
    vrows = rows[valid].astype(np.int64)
    vlabels = labels[valid]
    velapsed = elapsed[valid]
    shard = vrows // rows_per_shard
    order = np.argsort(shard, kind="stable")
    shard_sorted = shard[order]
    counts = np.bincount(shard_sorted, minlength=n_shards)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(len(shard_sorted), dtype=np.int64) - starts[shard_sorted]

    # overflow policy: a shard keeps its first batch_per_shard records in
    # arrival order; the rest are dropped and counted (the host must either
    # size batch_per_shard for the worst shard or re-send dropped records)
    keep = slot < batch_per_shard
    dropped = int(len(shard_sorted) - int(keep.sum()))
    src = order[keep]
    dst_shard = shard_sorted[keep]
    dst_slot = slot[keep]

    out_rows = np.zeros((n_shards, batch_per_shard), np.int32)
    out_labels = np.zeros((n_shards, batch_per_shard), np.int32)
    out_elapsed = np.zeros((n_shards, batch_per_shard), np.float32)
    out_valid = np.zeros((n_shards, batch_per_shard), bool)
    out_rows[dst_shard, dst_slot] = (vrows[src] % rows_per_shard).astype(np.int32)
    out_labels[dst_shard, dst_slot] = vlabels[src]
    out_elapsed[dst_shard, dst_slot] = velapsed[src]
    out_valid[dst_shard, dst_slot] = True
    return out_rows, out_labels, out_elapsed, out_valid, dropped
