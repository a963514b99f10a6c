"""Sequence (window-axis) sharding for extreme lag windows — the context-
parallelism mode.

The production layout shards the service axis only: at stock scale a whole
8640-step (24 h) window fits per chip, so sequence sharding is unnecessary
(SURVEY.md §5.7). But the lag window IS this system's sequence dimension, and
for extreme windows (multi-week lags, or huge per-service capacity squeezing
HBM) the z-score ring ``[S, 3, L]`` itself must split. This module shards it
over a 2-D ``(services, window)`` mesh:

- every window shard holds an ``L/W``-slice of each ring;
- the window statistics take five small collectives per step over [S, 3]
  partials — psum(count), psum(sum), pmin, pmax from one fused local pass,
  then psum(var partial) after the mean broadcast (sum/min/max cannot share
  one all-reduce combiner) — the reference's two-pass mean/std
  (util_methods.js:10-50) computed collectively. Results
  match the single-chip path to reduction-order rounding (the psum tree sums
  shard partials in a different order than one flat sum; last-ulp
  differences are inherent), which a one-pass sum/sumsq trick would degrade
  much further;
- the influence-damping lookup of the last pushed value and the ring write
  each touch exactly one owner shard, selected by masked psum / masked store;
- ``fill``/``pos`` counters are replicated across window shards and advance
  identically everywhere.

This is the all-reduce flavor of sequence parallelism (a ring/all-to-all
exchange is unnecessary because the reduction is a plain sum over the
sequence axis — no attention-style pairwise interaction exists).
Parity-tested against ops.zscore.step on the virtual CPU mesh, including the
exact degenerate-window (all-equal -> no std) semantics via pmin/pmax.
Assumes a fully-populated fleet (no per-row ``active`` gate): shard the rows
you have, not a padded registry.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..ops.zscore import (
    ZScoreConfig,
    ZScoreResult,
    ZScoreState,
    fused_window_partials,
)
from .mesh import SERVICE_AXIS

WINDOW_AXIS = "window"


def make_mesh2d(n_service_shards: int, n_window_shards: int) -> Mesh:
    devices = jax.devices()
    need = n_service_shards * n_window_shards
    if need > len(devices):
        raise ValueError(
            f"Requested a {n_service_shards}x{n_window_shards} mesh but only "
            f"{len(devices)} JAX device(s) are visible"
        )
    grid = np.array(devices[:need]).reshape(n_service_shards, n_window_shards)
    return Mesh(grid, (SERVICE_AXIS, WINDOW_AXIS))


def shard_zstate(state: ZScoreState, mesh: Mesh) -> ZScoreState:
    """Place values [S, 3, L] on (services, window); counters on services."""
    from jax.sharding import NamedSharding

    return ZScoreState(
        values=jax.device_put(state.values, NamedSharding(mesh, P(SERVICE_AXIS, None, WINDOW_AXIS))),
        fill=jax.device_put(state.fill, NamedSharding(mesh, P(SERVICE_AXIS))),
        pos=jax.device_put(state.pos, NamedSharding(mesh, P())),  # global scalar cursor
    )


def _local_step(cfg: ZScoreConfig, n_window_shards: int):
    """The per-shard body; cfg.lag is the GLOBAL lag length."""
    L = cfg.lag
    if L % n_window_shards != 0:
        raise ValueError(f"lag {L} not divisible by window shards {n_window_shards}")
    L_loc = L // n_window_shards

    def fn(state: ZScoreState, new_values, threshold, influence):
        widx = jax.lax.axis_index(WINDOW_AXIS)
        raw = state.values  # [S_loc, 3, L_loc] in storage dtype
        vals = raw.astype(cfg.dtype) if raw.dtype != cfg.dtype else raw
        fill, pos = state.fill, state.pos
        full = fill >= L

        # two-pass mean/std over the sharded window (reference parity); the
        # local partials come from ONE fused variadic reduce over the shard
        # slice (same trick as ops.zscore.step — this module serves the rings
        # too big for one chip, the most bandwidth-bound case of all)
        valid = ~jnp.isnan(vals)
        cnt_l, total_l, vmin_l, vmax_l = fused_window_partials(vals, valid)
        cnt = jax.lax.psum(cnt_l, WINDOW_AXIS)  # [S, 3]
        total = jax.lax.psum(total_l, WINDOW_AXIS)
        has_avg = (cnt > 0) & full[:, None]
        mean = jnp.where(has_avg, total / jnp.maximum(cnt, 1), jnp.nan)
        # degenerate (all-equal) windows resolved exactly, matching
        # ops.zscore.step: pmax/pmin over the fused local partials
        vmax = jax.lax.pmax(vmax_l, WINDOW_AXIS)
        vmin = jax.lax.pmin(vmin_l, WINDOW_AXIS)
        all_equal = has_avg & (vmax == vmin)
        mean = jnp.where(all_equal, vmax, mean)
        diff = jnp.where(valid, vals - mean[..., None], 0)
        var_sum = jax.lax.psum(jnp.sum(diff * diff, axis=-1), WINDOW_AXIS)
        var = jnp.where(has_avg, var_sum / jnp.maximum(cnt, 1), jnp.nan)
        has_std = has_avg & ~all_equal & (var > 0)
        std = jnp.where(has_std, jnp.sqrt(var), jnp.nan)

        thr = threshold[:, None]
        lb = jnp.where(has_std, mean - thr * std, jnp.nan)
        ub = jnp.where(has_std, mean + thr * std, jnp.nan)
        new_ok = ~jnp.isnan(new_values)
        exceeds = has_std & new_ok & (jnp.abs(new_values - mean) > thr * std)
        signal = jnp.where(exceeds, jnp.where(new_values > mean, 1, -1), 0).astype(jnp.int32)

        # last pushed value lives on exactly one window shard (the GLOBAL
        # scalar cursor means the same slot for every row): masked psum
        last_idx = (pos - 1) % L  # [] global slot
        owner = (last_idx // L_loc) == widx  # [] bool: this shard holds it
        lv = jax.lax.dynamic_slice_in_dim(vals, last_idx % L_loc, 1, axis=2)[..., 0]
        lv_nan = jnp.isnan(lv)
        last_val = jax.lax.psum(
            jnp.where(owner & ~lv_nan, lv, 0), WINDOW_AXIS
        )
        last_nan = (
            jax.lax.psum(jnp.where(owner, lv_nan.astype(jnp.int32), 0), WINDOW_AXIS) > 0
        )
        can_damp = exceeds & ~last_nan & (fill > 0)[:, None]
        infl = influence[:, None]
        pushed = jnp.where(can_damp, infl * new_values + (1 - infl) * last_val, new_values)

        # ring write: the owner shard stores, everyone else writes its slot's
        # current content back — the write stays ONE contiguous in-place
        # dynamic_update_slice on every shard (never a whole-ring select).
        # Write against the RAW ring so storage bits round-trip exactly.
        owner_w = (pos // L_loc) == widx  # [] bool
        lw = pos % L_loc
        cur = jax.lax.dynamic_slice_in_dim(raw, lw, 1, axis=2)[..., 0]
        store = jnp.where(owner_w, pushed.astype(raw.dtype), cur)
        new_vals = jax.lax.dynamic_update_slice_in_dim(raw, store[:, :, None], lw, axis=2)
        new_fill = jnp.minimum(fill + 1, L)
        new_pos = (pos + 1) % L

        result = ZScoreResult(
            window_avg=mean.astype(cfg.dtype),
            lower_bound=lb.astype(cfg.dtype),
            upper_bound=ub.astype(cfg.dtype),
            signal=signal,
        )
        return result, ZScoreState(new_vals, new_fill, new_pos)

    return fn


def make_window_sharded_step(mesh: Mesh, cfg: ZScoreConfig):
    """jit(shard_map(z-score step)) over a (services, window) mesh.

    ``cfg`` carries GLOBAL capacity and lag; both must divide by their mesh
    axis. Inputs/outputs: state as placed by :func:`shard_zstate`; per-row
    vectors (new_values, threshold, influence) sharded on services.
    """
    n_s = mesh.shape[SERVICE_AXIS]
    n_w = mesh.shape[WINDOW_AXIS]
    if cfg.robust:
        # median/MAD needs a distributed selection over the window axis (two
        # collective sorts), which this all-reduce layout does not implement;
        # robust lags at extreme-window scale should shard services only
        raise NotImplementedError(
            "robust (median/MAD) z-score is not supported with window-axis "
            "sharding; use service-axis sharding for robust lags"
        )
    if cfg.sliding_active:
        # the O(1) sliding aggregates make the per-tick window read vanish
        # entirely on a single chip, which removes THIS module's reason to
        # exist for most deployments (window sharding only still pays when
        # the ring itself exceeds one chip's HBM). The sharded step keeps
        # the exact collective two-pass; refuse the flag combination rather
        # than silently diverging from what the config asked for.
        raise NotImplementedError(
            "sliding aggregates are not implemented for window-axis "
            "sharding; set tpuEngine.zscoreVariancePass='two' for "
            "window-sharded lags (or drop window sharding — the sliding "
            "step no longer reads the window per tick)"
        )
    if cfg.onepass_var and cfg.dtype != jnp.float64:
        # this path computes the exact two-pass variance collectively;
        # silently ignoring the flag would let sharded and single-chip
        # deployments diverge beyond reduction-order rounding (the module's
        # parity contract) — refuse instead, like robust
        raise NotImplementedError(
            "one-pass variance is not implemented for window-axis sharding; "
            "set tpuEngine.zscoreVariancePass='two' for window-sharded lags"
        )
    if cfg.capacity % n_s != 0:
        raise ValueError(f"capacity {cfg.capacity} not divisible by service shards {n_s}")
    local_cfg = cfg._replace(capacity=cfg.capacity // n_s)
    fn = _local_step(local_cfg, n_w)

    state_spec = ZScoreState(
        values=P(SERVICE_AXIS, None, WINDOW_AXIS),
        fill=P(SERVICE_AXIS),
        pos=P(),
    )
    row2 = P(SERVICE_AXIS, None)
    row = P(SERVICE_AXIS)
    result_spec = ZScoreResult(
        window_avg=row2, lower_bound=row2, upper_bound=row2, signal=row2
    )
    mapped = shard_map(
        fn,
        mesh=mesh,
        in_specs=(state_spec, row2, row, row),
        out_specs=(result_spec, state_spec),
    )
    return jax.jit(mapped)
