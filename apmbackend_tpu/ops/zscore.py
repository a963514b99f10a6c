"""Device-resident smoothed z-score anomaly baselining (stream_calc_z_score rebuild).

The reference keeps, per (server, service, lag), three rolling JS arrays
(avg/p75/p95 histories) and on every StatEntry recomputes mean + population
std over the whole window, derives bounds avg ± threshold*std, emits a signal
in {-1, 0, +1}, and appends an influence-damped value
(stream_calc_z_score.js:66-104, 195-311). Here the state is a dense ring
``values [S, 3, L]`` and the whole key space steps in one fused XLA program.

Quirk parity (tested against the float64 host oracle in tests/):
- Warm-up gating is on *raw pushed length* (including NaN entries):
  ``prevValuesList.length >= lag`` (stream_calc_z_score.js:75) — modeled by a
  per-row ``fill`` counter; all 3 metric lists always share one length.
- mean/std skip NaN entries (util_methods.js:10-50); all-NaN -> undefined.
- zero variance -> std undefined -> no bounds, no signal
  (util_methods.js:44-48).
- signal iff |new - avg| > threshold*std strictly; NaN new value -> 0.
- influence damping applies only when a signal fired AND the most recently
  pushed value is non-NaN (stream_calc_z_score.js:96-97); the *damped* value
  is what enters the ring.
- stats are computed over the window BEFORE the shift+push.

The per-step cost depends on the variance mode:
- two-pass / one-pass: a masked reduction over the whole [S, 3, L] ring —
  bandwidth-bound; the exactness baseline.
- sliding (``ZScoreConfig.sliding``, the production default): O(S*3) per
  step. Per-row running aggregates (valid count, raw sum, anchored sum of
  squares) are maintained incrementally — the evicted value is read from
  the single ring slot being overwritten, the pushed value is added — so
  the step never reads the ring beyond two one-element-per-row gathers.
  The ring becomes write-mostly cold storage whose only remaining jobs are
  exact periodic rebuilds (every ``rebuild_every`` ticks, one fused pass,
  cancelling float drift) and snapshot/restore (the aggregate is DERIVED
  state: ``restore_agg`` reconstructs it from the ring, so resume files
  keep their schema). The zero-variance quirk stays EXACT via a run-length
  counter: the window's valid entries are precisely the last ``cnt`` valid
  pushes, so "all window values equal" ⟺ "the maximal equal suffix of
  valid pushes covers them" (``run_len >= cnt``) — no min/max scan needed.
  f64 parity mode and robust (median/MAD) lags never take this branch.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

N_METRICS = 3  # average, per75, per95 (in that order on axis 1)


class ZScoreConfig(NamedTuple):
    capacity: int  # S
    lag: int  # L (window length in intervals)
    dtype: jnp.dtype = jnp.float32
    # robust mode (no reference equivalent): baseline = window median, spread
    # = 1.4826 * MAD instead of mean/std. The classic z-score's weakness is
    # self-contamination — past outliers inflate the window std and mask
    # later anomalies until they age out of the lag window; median/MAD has a
    # 50% breakdown point, so bounds stay tight through outlier bursts. Costs
    # two sorts over [S, 3, L] per step instead of one reduction.
    robust: bool = False
    # STORAGE dtype of the values ring; None = same as ``dtype``. The ring is
    # the engine's dominant HBM buffer ([S, 3, L]: ~850 MB/tick of read
    # traffic at 8192 rows x lag 8640 in f32), and the step is bandwidth-
    # bound — storing it bfloat16 halves that traffic while every statistic
    # still accumulates in ``dtype`` (values upcast in-register on load, the
    # standard TPU mixed-precision pattern). Costs ~0.4% relative rounding
    # on stored values; gating semantics (warm-up, NaN, zero-variance,
    # all-equal) are dtype-exact either way.
    ring_dtype: jnp.dtype = None
    # Variance in ONE ring pass instead of two: sum of (x - K)^2 rides the
    # same variadic reduce as count/sum/min/max with the per-row anchor K =
    # last pushed value, then var = E[(x-K)^2] - (mean-K)^2. The anchor sits
    # inside the window's range, so the shifted squares are small and the
    # cancellation benign (measured <= ~1e-5 relative var error in f32;
    # 1.36x on the CPU reduce, ~2x of HBM read traffic saved on TPU). The
    # degenerate all-equal guard stays EXACT (min == max), so the
    # zero-variance quirk cannot flip. Two-pass remains the exactness
    # baseline; f64 parity mode must keep it.
    onepass_var: bool = False
    # O(1)-per-step incremental window aggregates (module docstring). Takes
    # precedence over onepass_var; silently inert in f64 parity mode and for
    # robust lags (both need/keep the full-window computation).
    sliding: bool = False
    # exact full-ring rebuild cadence for the sliding aggregates (ticks);
    # bounds float drift. Amortized cost = 1/rebuild_every of one ring pass.
    rebuild_every: int = 64

    @property
    def storage_dtype(self):
        return self.ring_dtype if self.ring_dtype is not None else self.dtype

    @property
    def sliding_active(self) -> bool:
        return bool(self.sliding) and self.dtype != jnp.float64 and not self.robust


class SlidingAgg(NamedTuple):
    """Incremental window aggregates for ``ZScoreConfig.sliding`` mode.

    Everything here is derived from the values ring (``restore_agg``), so it
    is never serialized; restore rebuilds it. Invariants between rebuilds:
    ``cnt`` is the count of valid (non-NaN) window entries; ``vsum``/
    ``vsumsq`` are the sums of (x - anchor) and (x - anchor)^2 over them,
    with the per-row ``anchor`` frozen since the last rebuild (or the row's
    first value). ANCHORED moments keep every accumulated quantity at
    data-spread scale — mean = anchor + vsum/cnt, var = vsumsq/cnt -
    (vsum/cnt)^2 — so neither the raw-sum ulp loss (magnitude ~1e6 windows)
    nor the E[x^2] - mean^2 cancellation can poison f32 variance.
    ``run_len`` is the equal-suffix length of valid pushes (capped at L + 1),
    so run_len >= cnt ⟺ window all-equal; ``last_valid`` is the most recent
    non-NaN pushed value (storage-rounded).
    """

    cnt: jnp.ndarray  # [S, 3] int32
    vsum: jnp.ndarray  # [S, 3] dtype
    vsumsq: jnp.ndarray  # [S, 3] dtype
    anchor: jnp.ndarray  # [S, 3] dtype
    run_len: jnp.ndarray  # [S, 3] int32
    last_valid: jnp.ndarray  # [S, 3] dtype (NaN = no valid push yet)
    # mirror of ring slot g-1 per row: the most recent push INCLUDING NaN
    # pushes (storage-rounded, so it equals the ring bits exactly). Lets the
    # core step obtain the damping reference without touching the ring — on
    # XLA:CPU any read of a donated buffer in the same program as its
    # in-place update forces a whole-buffer copy (measured 736 ms vs 0.6 ms
    # at [8192, 3, 8640]), so the staged path keeps the ring write in a
    # read-free program (ring_write) and everything else ring-free.
    # (The rebuild cadence is counted on the HOST — PipelineDriver/bench —
    # so no device-side clock leaf rides the donated step.)
    last_push: jnp.ndarray  # [S, 3] dtype (NaN = never pushed / NaN push)


class ZScoreState(NamedTuple):
    values: jnp.ndarray  # [S, 3, L] ring (NaN where never written)
    fill: jnp.ndarray  # [S] int32: list length (0..L)
    # GLOBAL write cursor (scalar): next slot every row writes. Per-row
    # cursors are unnecessary — active rows push every tick and activation
    # is permanent, so rows share one rotation; a scalar cursor turns the
    # ring write into a contiguous (in-place-aliasing) dynamic_update_slice
    pos: jnp.ndarray  # [] int32
    agg: Optional[SlidingAgg] = None  # present iff cfg.sliding_active


def _zero_agg(cfg: ZScoreConfig) -> SlidingAgg:
    S = cfg.capacity
    dt = cfg.dtype
    # distinct arrays per leaf: the engine tick donates its state, and three
    # leaves aliasing one zeros buffer is a double-donation runtime error
    return SlidingAgg(
        cnt=jnp.zeros((S, N_METRICS), jnp.int32),
        vsum=jnp.zeros((S, N_METRICS), dt),
        vsumsq=jnp.zeros((S, N_METRICS), dt),
        anchor=jnp.zeros((S, N_METRICS), dt),
        run_len=jnp.zeros((S, N_METRICS), jnp.int32),
        last_valid=jnp.full((S, N_METRICS), jnp.nan, dt),
        last_push=jnp.full((S, N_METRICS), jnp.nan, dt),
    )


def init_state(cfg: ZScoreConfig) -> ZScoreState:
    S, L = cfg.capacity, cfg.lag
    return ZScoreState(
        values=jnp.full((S, N_METRICS, L), jnp.nan, cfg.storage_dtype),
        fill=jnp.zeros((S,), jnp.int32),
        pos=jnp.zeros((), jnp.int32),
        agg=_zero_agg(cfg) if cfg.sliding_active else None,
    )


def build_agg(values: jnp.ndarray, cfg: ZScoreConfig, pos=None, anchor=None) -> SlidingAgg:
    """Exact SlidingAgg from a values ring (periodic rebuild; the restore
    path's ``restore_agg`` builds on it).

    Without ``anchor``: two fused passes — the first finds the window mean
    to anchor around, the second takes the anchored sums (the restore path,
    which has no prior estimate). With ``anchor`` (a [S, 3] estimate, e.g.
    the incremental mean at rebuild time): ONE pass — any anchor inside the
    window's value range keeps the moment cancellation benign, so an
    estimate is as good as the exact mean and the rebuild halves its ring
    traffic. ``pos`` (the global cursor; 0 when omitted) locates slot g-1
    for the ``last_push`` mirror. ``run_len``/``last_valid`` come out only
    for all-equal windows (min == max) and 0/NaN elsewhere: a rebuild keeps
    the step's own counters for those rows (``rebuild_agg_state``), and a
    restore scans for them (``restore_agg``)."""
    L = values.shape[-1]
    vals = values.astype(cfg.dtype) if values.dtype != cfg.dtype else values
    valid = ~jnp.isnan(vals)
    if anchor is None:
        cnt0, total0, _, _ = fused_window_partials(vals, valid)
        anchor = jnp.where(cnt0 > 0, total0 / jnp.maximum(cnt0, 1), 0)
    anchor = anchor.astype(cfg.dtype)
    cnt, total, sumsq, vmin, vmax = fused_window_partials_sq(vals, valid, anchor[..., None])
    all_eq = (cnt > 0) & (vmin == vmax)
    g = jnp.zeros((), jnp.int32) if pos is None else jnp.asarray(pos, jnp.int32)
    last_push = jax.lax.dynamic_slice_in_dim(vals, (g - 1) % L, 1, axis=2)[..., 0]
    return SlidingAgg(
        cnt=cnt.astype(jnp.int32),
        vsum=total.astype(cfg.dtype),
        vsumsq=sumsq.astype(cfg.dtype),
        anchor=anchor,
        run_len=jnp.where(all_eq, cnt, 0).astype(jnp.int32),
        last_valid=jnp.where(all_eq, vmax, jnp.nan).astype(cfg.dtype),
        last_push=last_push.astype(cfg.dtype),
    )


def restore_agg(values: jnp.ndarray, cfg: ZScoreConfig, pos) -> SlidingAgg:
    """``build_agg`` with the run-length guard made exact: the restore-time
    derivation (pipeline.engine_derive_aggs), run once per restore.

    ``last_valid`` is the newest valid push and ``run_len`` the length of
    the equal suffix of valid pushes, both read back in push order from
    slot ``pos - 1``. ``build_agg`` can only prove all-equal rows and
    leaves the others at 0, which misses a list whose older entries age
    out until its equal suffix is all that is left (say, a key idle for a
    lag after a restart): the engine would band it on float residue where
    the upstream sees zero variance. The per-tick rebuilds keep
    ``build_agg``: between rebuilds the step keeps the counters exact."""
    L = values.shape[-1]
    vals = values.astype(cfg.dtype) if values.dtype != cfg.dtype else values
    valid = ~jnp.isnan(vals)
    age = (jnp.asarray(pos, jnp.int32) - 1 - jnp.arange(L, dtype=jnp.int32)) % L  # 0 = newest
    newest = jnp.min(jnp.where(valid, age, L), axis=-1, keepdims=True)
    last_valid = jnp.max(jnp.where(valid & (age == newest), vals, -jnp.inf), axis=-1)
    last_valid = jnp.where(newest[..., 0] < L, last_valid, jnp.nan)
    other = valid & (vals != last_valid[..., None])
    first_other = jnp.min(jnp.where(other, age, L), axis=-1, keepdims=True)
    run_len = jnp.sum(valid & (age < first_other), axis=-1, dtype=jnp.int32)
    return build_agg(values, cfg, pos)._replace(run_len=run_len, last_valid=last_valid)


def normalize_legacy_ring(values_np, fill_np, pos_np, L: int):
    """Host-side migration of a PRE-global-cursor snapshot (per-row cursors,
    pos shape [S]): rotate each row so its next-write slot lands on the
    shared cursor 0. Window content and eviction order are rotation-
    invariant, so the migrated engine is bit-equivalent to the legacy
    layout. Returns the rotated [S, 3, L] numpy array; the caller sets the
    scalar cursor to 0. Shared by the npz load_resume and the orbax
    checkpoint restore so the migration math cannot drift."""
    import numpy as np

    w = np.where(
        fill_np >= L,
        pos_np.astype(np.int64),
        np.minimum(fill_np, L - 1).astype(np.int64),
    )
    j = (np.arange(L)[None, :] + w[:, None]) % L  # [S, L]
    return np.take_along_axis(values_np, j[:, None, :], axis=2)


def rebuild_agg_state(state: ZScoreState, cfg: ZScoreConfig) -> ZScoreState:
    """Amortized exact rebuild of the sliding aggregates — called from the
    HOST loop every ``cfg.rebuild_every`` ticks (pipeline.engine_rebuild_aggs;
    it cannot ride inside the jitted step, whose contract is to never touch
    the whole ring). Cancels float drift in the running sums and refreshes
    the variance anchor to the current mean. ``run_len``/``last_valid``
    keep the step's counters except on rows ``build_agg`` proves all-equal,
    which take its values; the counters are exact since the restore
    (``restore_agg``), so that merge decides no guard differently.
    No-op for non-sliding configs."""
    if not cfg.sliding_active or state.agg is None:
        return state
    old = state.agg
    # the incremental mean is a perfectly good anchor (it only needs to sit
    # inside the window's value range) — passing it makes the rebuild ONE
    # ring pass instead of two
    anchor_est = jnp.where(
        old.cnt > 0, old.anchor + old.vsum / jnp.maximum(old.cnt, 1), old.anchor
    )
    fresh = build_agg(state.values, cfg, state.pos, anchor_est)
    # rows build_agg proves all-equal (min==max) take the repaired run;
    # everything else keeps the incrementally-exact counters
    proved = fresh.run_len > 0
    agg = fresh._replace(
        run_len=jnp.where(proved, fresh.run_len, old.run_len),
        last_valid=jnp.where(proved, fresh.last_valid, old.last_valid),
    )
    return state._replace(agg=agg)


def rebuild_chunk_rows(capacity: int, rebuild_every: int) -> int:
    """Row-chunk size of the STAGGERED rebuild schedule: the whole ring is
    re-aggregated once per ``rebuild_every`` ticks, one contiguous row chunk
    per tick, so the worst tick pays ~1/rebuild_every of the full pass
    instead of one tick absorbing it all (the monolithic rebuild_agg_state
    stalled a tick for seconds at pod shapes). Every row's rebuild interval
    stays <= rebuild_every ticks — the drift bound is unchanged."""
    return max(1, -(-capacity // max(rebuild_every, 1)))


def build_agg_slice_partials(state: ZScoreState, cfg: ZScoreConfig, row_start, chunk: int):
    """Fresh anchored window moments for ring rows [row_start, row_start+chunk)
    — the in-program (XLA) partial producer of the staggered rebuild. Returns
    ``(cnt, vsum, vsumsq, anchor, vmin, vmax, last_push)``, each [chunk, 3].
    Per-row math is identical to build_agg's single-anchor pass (rows are
    independent under the last-axis reduce), so applying every chunk of a
    cycle reproduces rebuild_agg_state BITWISE. ``chunk`` is static;
    ``row_start`` is traced (one compiled program serves the whole rotation).
    """
    old = state.agg
    vals = jax.lax.dynamic_slice_in_dim(state.values, row_start, chunk, axis=0)
    if vals.dtype != cfg.dtype:
        vals = vals.astype(cfg.dtype)
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, row_start, chunk, axis=0)
    # the incremental mean is a valid variance anchor (rebuild_agg_state)
    cnt_o, vsum_o, anchor_o = sl(old.cnt), sl(old.vsum), sl(old.anchor)
    anchor = jnp.where(
        cnt_o > 0, anchor_o + vsum_o / jnp.maximum(cnt_o, 1), anchor_o
    ).astype(cfg.dtype)
    valid = ~jnp.isnan(vals)
    cnt, vsum, vsumsq, vmin, vmax = fused_window_partials_sq(vals, valid, anchor[..., None])
    L = state.values.shape[-1]
    g = jnp.asarray(state.pos, jnp.int32)
    last_push = jax.lax.dynamic_slice_in_dim(vals, (g - 1) % L, 1, axis=2)[..., 0]
    return (cnt.astype(jnp.int32), vsum.astype(cfg.dtype), vsumsq.astype(cfg.dtype),
            anchor, vmin, vmax, last_push.astype(cfg.dtype))


def merge_agg_slice(
    agg: SlidingAgg, cfg: ZScoreConfig, row_start,
    cnt, vsum, vsumsq, anchor, vmin, vmax, last_push,
) -> SlidingAgg:
    """Fold freshly-rebuilt chunk partials (either producer: the XLA slice
    pass above or the native streaming kernel) back into the full [S, 3]
    aggregates. ONE merge implementation so the two producers cannot drift:
    the all-equal proof (min == max) repairs run_len/last_valid exactly as
    rebuild_agg_state does; unproved rows keep their incrementally-exact
    counters. All leaves are [S, 3] — the DUS writes are noise next to the
    ring pass they retire."""
    dt = cfg.dtype
    all_eq = (cnt > 0) & (vmin == vmax)
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, row_start, cnt.shape[0], axis=0)
    run_len = jnp.where(all_eq, cnt, sl(agg.run_len)).astype(jnp.int32)
    last_valid = jnp.where(all_eq, vmax, sl(agg.last_valid)).astype(dt)
    up = lambda full, part: jax.lax.dynamic_update_slice_in_dim(
        full, part.astype(full.dtype), row_start, axis=0
    )
    return SlidingAgg(
        cnt=up(agg.cnt, cnt),
        vsum=up(agg.vsum, vsum),
        vsumsq=up(agg.vsumsq, vsumsq),
        anchor=up(agg.anchor, anchor),
        run_len=up(agg.run_len, run_len),
        last_valid=up(agg.last_valid, last_valid),
        last_push=up(agg.last_push, last_push),
    )


def rebuild_agg_slice(state: ZScoreState, cfg: ZScoreConfig, row_start, chunk: int) -> ZScoreState:
    """One staggered-rebuild step: exact re-aggregation of ring rows
    [row_start, row_start+chunk) only (rebuild_chunk_rows sizes the chunk so
    a full rotation spans cfg.rebuild_every ticks). The host loop clamps
    row_start to capacity-chunk, so when chunk does not divide capacity the
    tail chunk overlaps a few already-rebuilt rows — exact but not bitwise
    for those rows (their second rebuild derives its anchor from the
    just-refreshed aggregates). When chunk divides capacity, applying all
    chunks back-to-back is BITWISE rebuild_agg_state; ragged capacities are
    value-exact (both tested). No-op for non-sliding configs."""
    if not cfg.sliding_active or state.agg is None:
        return state
    parts = build_agg_slice_partials(state, cfg, row_start, chunk)
    return state._replace(agg=merge_agg_slice(state.agg, cfg, row_start, *parts))


def _fused_reduce(vals: jnp.ndarray, valid: jnp.ndarray, anchor=None):
    """ONE variadic lax.reduce over the last axis. Without ``anchor``:
    (count, raw sum, min, max). With ``anchor``: (count, shifted sum,
    shifted sumsq, min, max) — BOTH moments are taken around the per-row
    anchor, so every accumulated quantity lives at data-SPREAD scale: a raw
    f32 sum of lag-8640 windows at magnitude ~1e6 carries ~0.5 of ulp error,
    which poisons mean (and then variance) exactly where variance is small;
    the shifted sum is ~0 +- spread and stays exact. The single builder
    serves the two-pass, one-pass and sliding paths so their masking/init
    semantics cannot drift."""
    dt = vals.dtype
    if anchor is None:
        operands = [valid.astype(jnp.int32), jnp.where(valid, vals, 0)]
        inits = [jnp.int32(0), jnp.array(0, dt)]
    else:
        sh = jnp.where(valid, vals - anchor, 0)
        operands = [valid.astype(jnp.int32), sh, sh * sh]
        inits = [jnp.int32(0), jnp.array(0, dt), jnp.array(0, dt)]
    operands += [jnp.where(valid, vals, jnp.inf), jnp.where(valid, vals, -jnp.inf)]
    inits += [jnp.array(jnp.inf, dt), jnp.array(-jnp.inf, dt)]
    n_sum = len(inits) - 2

    def combine(a, b):
        out = tuple(a[i] + b[i] for i in range(n_sum))
        return out + (jnp.minimum(a[n_sum], b[n_sum]), jnp.maximum(a[n_sum + 1], b[n_sum + 1]))

    return jax.lax.reduce(tuple(operands), tuple(inits), combine, [vals.ndim - 1])


def fused_window_partials(vals: jnp.ndarray, valid: jnp.ndarray):
    """(count, sum, min, max) in one pass (3.2x measured vs four passes on
    the bandwidth-bound CPU path). Shared by the single-chip step and the
    window-sharded local step so the two paths cannot drift."""
    return _fused_reduce(vals, valid)


def fused_window_partials_sq(vals: jnp.ndarray, valid: jnp.ndarray, anchor: jnp.ndarray):
    """(count, shifted-sum, shifted-sumsq, min, max) in ONE pass — the
    anchored-moments variant (one-pass variance and the sliding rebuild):
    ``anchor`` is a per-row ``[..., 1]``-broadcastable constant BOTH moments
    are taken around; mean = anchor + ssum/cnt, var = ssumsq/cnt -
    (ssum/cnt)^2."""
    return _fused_reduce(vals, valid, anchor)


def _median_from_sorted(s: jnp.ndarray, cnt: jnp.ndarray) -> jnp.ndarray:
    """NaN-aware median over the last axis of an ascending-sorted array (NaN
    tail) with ``cnt`` valid entries per row; NaN where cnt == 0."""
    K = s.shape[-1]
    i1 = jnp.clip((cnt - 1) // 2, 0, K - 1)
    i2 = jnp.clip(cnt // 2, 0, K - 1)
    v1 = jnp.take_along_axis(s, i1[..., None], axis=-1)[..., 0]
    v2 = jnp.take_along_axis(s, i2[..., None], axis=-1)[..., 0]
    return jnp.where(cnt > 0, (v1 + v2) / 2, jnp.nan)


# MAD -> sigma consistency constant for normal data (1 / Phi^-1(3/4)): with
# it the robust bounds coincide with the classic ones on clean gaussian
# windows, so a per-lag THRESHOLD keeps one meaning across both modes
MAD_SIGMA = 1.4826


class ZScoreResult(NamedTuple):
    # each [S, 3] on the metric axis (average, per75, per95)
    window_avg: jnp.ndarray  # NaN = undefined
    lower_bound: jnp.ndarray
    upper_bound: jnp.ndarray
    signal: jnp.ndarray  # int32 in {-1, 0, 1}


def _emit_and_damp(
    cfg, mean, std, has_std, new_values, threshold, influence, last_val, fill
):
    """The parity-critical gating tail shared by every single-chip mode
    (sliding step_core and the full-window step): bounds, strict-exceed
    signal, and influence damping. ONE source of truth so the modes cannot
    desynchronize. Returns (ZScoreResult, pushed [S, 3] in cfg.dtype).
    (window_sharded._local_step keeps its own copy: its last-value NaNness
    arrives as a separate psum'd flag, not as NaN in last_val.)"""
    thr = threshold[:, None]
    lb = jnp.where(has_std, mean - thr * std, jnp.nan)
    ub = jnp.where(has_std, mean + thr * std, jnp.nan)
    new_ok = ~jnp.isnan(new_values)
    exceeds = has_std & new_ok & (jnp.abs(new_values - mean) > thr * std)
    signal = jnp.where(exceeds, jnp.where(new_values > mean, 1, -1), 0).astype(jnp.int32)
    # influence damping: only on signal and when the most recent push is
    # defined (NaN last_val == never pushed or NaN push)
    can_damp = exceeds & ~jnp.isnan(last_val) & (fill > 0)[:, None]
    infl = influence[:, None]
    pushed = jnp.where(can_damp, infl * new_values + (1 - infl) * last_val, new_values)
    result = ZScoreResult(
        window_avg=mean.astype(cfg.dtype),
        lower_bound=lb.astype(cfg.dtype),
        upper_bound=ub.astype(cfg.dtype),
        signal=signal,
    )
    return result, pushed


def ring_evict_read(values: jnp.ndarray, pos) -> jnp.ndarray:
    """[S, 3] content of the slot the next push will overwrite (the oldest
    entry; NaN where nothing was evicted). MUST be dispatched in a program
    that does not also write the ring (module staging contract)."""
    return jax.lax.dynamic_slice_in_dim(values, pos, 1, axis=2)[..., 0]


def ring_write(values: jnp.ndarray, pushed: jnp.ndarray, pos) -> jnp.ndarray:
    """Store this tick's [S, 3] pushes at the global cursor. The ONLY ring
    op in its program: one contiguous dynamic_update_slice with no reads, so
    a donated call updates the [S, 3, L] buffer in place (0.6 ms vs 736 ms
    with any same-program read at [8192, 3, 8640] on XLA:CPU)."""
    return jax.lax.dynamic_update_slice_in_dim(
        values, pushed[:, :, None].astype(values.dtype), pos, axis=2
    )


def step_core(
    state: ZScoreState,
    cfg: ZScoreConfig,
    new_values: jnp.ndarray,  # [S, 3]
    threshold: jnp.ndarray,  # [S]
    influence: jnp.ndarray,  # [S]
    active: jnp.ndarray,  # [S] bool
    evicted: jnp.ndarray,  # [S, 3] from ring_evict_read (storage dtype)
) -> Tuple[ZScoreResult, ZScoreState, jnp.ndarray]:
    """The ring-free sliding step: window statistics, signal, damping and
    the incremental aggregate update, all from [S, 3] state. Returns
    (result, state-with-UNTOUCHED-ring, pushed) — the caller owes a
    ring_write(state.values, pushed, old pos) to complete the tick. step()
    composes the three pieces into one program (shard_map use); staged hosts
    dispatch them separately so the ring write stays in-place (module
    docstring)."""
    assert cfg.sliding_active, "step_core is the sliding-mode path"
    S, L = cfg.capacity, cfg.lag
    agg = state.agg
    fill = state.fill
    full = fill >= L  # [S] — signal eligibility (raw length incl. NaN pushes)
    g = state.pos  # [] int32: this tick's write slot

    # O(1) window statistics straight from the running ANCHORED moments:
    # mean = anchor + E[x - K], var = E[(x-K)^2] - E[x-K]^2 — everything
    # accumulates at data-spread scale (SlidingAgg docstring)
    cnt = agg.cnt  # [S, 3]
    has_avg = (cnt > 0) & full[:, None]
    mdelta = agg.vsum / jnp.maximum(cnt, 1)
    mean_raw = agg.anchor + mdelta
    # the EXACT zero-variance guard: window all-equal ⟺ the equal suffix
    # of valid pushes covers every valid entry
    all_equal = has_avg & (agg.run_len >= cnt)
    mean = jnp.where(all_equal, agg.last_valid, jnp.where(has_avg, mean_raw, jnp.nan))
    var = agg.vsumsq / jnp.maximum(cnt, 1) - mdelta**2
    var = jnp.where(has_avg, jnp.maximum(var, 0), jnp.nan)
    has_std = has_avg & ~all_equal & (var > 0)
    std = jnp.where(has_std, jnp.sqrt(var), jnp.nan)

    # agg.last_push mirrors ring slot g-1 exactly — no ring read needed
    result, pushed_f = _emit_and_damp(
        cfg, mean, std, has_std, new_values, threshold, influence,
        agg.last_push, fill,
    )
    # inactive rows push NaN: their ring is all-NaN (activation is permanent
    # and history starts at registration), so NaN keeps the slot's content —
    # without the read-back the old scatter path needed
    pushed = jnp.where(active[:, None], pushed_f, jnp.nan)
    # what the ring will actually hold (storage-rounded, e.g. bf16): the
    # aggregates must ingest these exact bits or the periodic rebuild from
    # the ring would disagree with the incremental sums
    v_new = pushed.astype(cfg.storage_dtype).astype(cfg.dtype)
    w_old = evicted.astype(cfg.dtype) if evicted.dtype != cfg.dtype else evicted

    add = ~jnp.isnan(v_new)  # NaN == inactive or NaN push: no aggregate entry
    sub = ~jnp.isnan(w_old)
    # a row's FIRST value becomes its variance anchor: re-anchoring is only
    # legal while the window holds no valid entries (cnt == 0, the anchored
    # sums are empty — there is nothing accumulated under the old anchor to
    # go stale), and there it is exact. Every row then carries a data-scale
    # anchor even on hosts that never call rebuild_agg_state, so the
    # catastrophic E[x^2] - mean^2 cancellation (anchor 0 on large-magnitude
    # rows) cannot occur. BOTH deltas below must use the post-re-anchor
    # value: the first push contributes (v0 - v0)^2 = 0, and no eviction can
    # coincide with cnt == 0 (an all-invalid window evicts only NaN).
    # Periodic rebuilds still re-tighten the anchor to the window mean.
    anchor2 = jnp.where((cnt == 0) & add, v_new, agg.anchor)
    cnt2 = cnt + add.astype(jnp.int32) - sub.astype(jnp.int32)
    da = jnp.where(add, v_new - anchor2, 0)
    db = jnp.where(sub, w_old - anchor2, 0)
    vsum2 = agg.vsum + da - db
    vsumsq2 = agg.vsumsq + da * da - db * db
    # a drained window (cnt back to 0) zeroes its sums EXACTLY: add/sub
    # round-trips can leave ulp-scale residue that would otherwise seed the
    # next fill-up (and the cnt==0 re-anchor assumes empty sums)
    empty = cnt2 == 0
    vsum2 = jnp.where(empty, 0, vsum2)
    vsumsq2 = jnp.where(empty, 0, vsumsq2)
    run2 = jnp.where(
        add,
        jnp.where(v_new == agg.last_valid, jnp.minimum(agg.run_len + 1, L + 1), 1),
        agg.run_len,
    )
    lastv2 = jnp.where(add, v_new, agg.last_valid)
    lastp2 = jnp.where(active[:, None], v_new, agg.last_push)
    # exact periodic rebuild cadence is counted by the HOST loop
    # (rebuild_agg_state cannot ride in-program — holding the ring in an
    # unexecuted cond branch forces a whole-ring copy on CPU)
    new_agg = SlidingAgg(cnt2, vsum2, vsumsq2, anchor2, run2, lastv2, lastp2)

    new_fill = jnp.where(active, jnp.minimum(fill + 1, L), fill)
    new_state = ZScoreState(state.values, new_fill, (g + 1) % L, new_agg)
    return result, new_state, pushed


def step(
    state: ZScoreState,
    cfg: ZScoreConfig,
    new_values: jnp.ndarray,  # [S, 3]: this tick's average/per75/per95 per row
    threshold: jnp.ndarray,  # [S]
    influence: jnp.ndarray,  # [S]
    active=None,  # [S] bool: rows that exist in the registry (None = all)
) -> Tuple[ZScoreResult, ZScoreState]:
    """``active`` gates the warm-up: the reference creates a key's rolling
    lists at the key's FIRST StatEntry, so a service first seen mid-run waits
    a full lag window before signalling. Without the mask every dense row
    accrues ``fill`` from engine start and a late-registered service would
    open its warm-up gate up to ``lag`` ticks early (z-score bounds over a
    near-empty window — false alerts on fresh deploys)."""
    S, L = cfg.capacity, cfg.lag
    if active is None:
        active = jnp.ones((S,), bool)
    raw = state.values  # [S, 3, L] in storage dtype (possibly bf16)

    if cfg.sliding_active:
        # single-program composition (shard_map / tests). NOTE on XLA:CPU
        # this pays one ring copy because the program both reads (evict)
        # and writes the ring; latency-critical hosts dispatch the three
        # pieces separately instead (pipeline.make_engine_step).
        g = state.pos
        evicted = ring_evict_read(raw, g)
        result, new_state, pushed = step_core(
            state, cfg, new_values, threshold, influence, active, evicted
        )
        return result, new_state._replace(values=ring_write(raw, pushed, g))

    # ---- full-window modes (two-pass / one-pass / robust) ----------------
    # upcast on load: XLA reads the narrow ring from HBM and converts
    # in-register, so all statistics below accumulate in cfg.dtype
    vals = raw.astype(cfg.dtype) if raw.dtype != cfg.dtype else raw
    fill = state.fill  # [S]
    full = fill >= L  # [S] — signal eligibility (raw length incl. NaN pushes)

    # last pushed value: needed by influence damping, and (one-pass mode) as
    # the variance anchor. The cursor is GLOBAL (scalar): every row writes
    # the same slot each tick (active rows push, inactive rows keep NaN), so
    # "the row's newest entry" is slot g-1 for every row — a contiguous
    # dynamic_slice, not a per-row gather.
    g = state.pos  # [] int32: this tick's write slot
    last_idx = (g - 1) % L
    last_val = jax.lax.dynamic_slice_in_dim(vals, last_idx, 1, axis=2)[..., 0]  # [S, 3]

    valid = ~jnp.isnan(vals)  # [S, 3, L]
    if cfg.robust:
        # median/MAD baseline: same gating quirks as the classic mode (warm-up
        # on raw fill, zero spread -> no signal, NaN new value -> no signal)
        cnt = jnp.sum(valid.astype(jnp.int32), axis=-1)  # [S, 3]
        has_avg = (cnt > 0) & full[:, None]
        mean = jnp.where(has_avg, _median_from_sorted(jnp.sort(vals, axis=-1), cnt), jnp.nan)
        dev = jnp.where(valid, jnp.abs(vals - mean[..., None]), jnp.nan)
        mad = _median_from_sorted(jnp.sort(dev, axis=-1), cnt)
        has_std = has_avg & (mad > 0)  # MAD==0 == the zero-variance quirk
        std = jnp.where(has_std, MAD_SIGMA * mad, jnp.nan)
    elif cfg.onepass_var and cfg.dtype != jnp.float64:
        # single ring pass: shifted sumsq rides the fused reduce. The anchor
        # must sit inside the window's value range for the
        # E[(x-K)^2] - (mean-K)^2 cancellation to stay benign, INCLUDING
        # right after a data gap (a NaN push makes last_val NaN — a bare
        # 0 fallback there reintroduces the catastrophic E[x^2] - mean^2
        # cancellation for large-magnitude rows). So the anchor is the
        # nanmean of last_val plus 8 strided ring slots: a [S, 3, 8] gather,
        # negligible next to the [S, 3, L] pass it protects. All-NaN
        # candidates (=> near-empty window) fall back to 0. f64 parity mode
        # never takes this branch.
        stride_idx = jnp.arange(8, dtype=jnp.int32) * max(L // 8, 1) % L  # [8]
        samples = vals[:, :, stride_idx]  # [S, 3, 8]
        cand = jnp.concatenate([samples, last_val[..., None]], axis=-1)
        cand_ok = ~jnp.isnan(cand)
        n_cand = jnp.sum(cand_ok, axis=-1)
        anchor = jnp.where(
            n_cand > 0,
            jnp.sum(jnp.where(cand_ok, cand, 0), axis=-1) / jnp.maximum(n_cand, 1),
            0,
        )[..., None]
        cnt, ssum, sumsq, vmin, vmax = fused_window_partials_sq(vals, valid, anchor)
        has_avg = (cnt > 0) & full[:, None]
        # anchored moments throughout: mean = K + E[x-K], var = E[(x-K)^2]
        # - E[x-K]^2 — no raw 1e6-scale sum ever accumulates
        mdelta = ssum / jnp.maximum(cnt, 1)
        mean = jnp.where(has_avg, anchor[..., 0] + mdelta, jnp.nan)
        # the all-equal guard stays EXACT (min == max): the zero-variance
        # quirk cannot flip on float noise in this mode either
        all_equal = has_avg & (vmax == vmin)
        mean = jnp.where(all_equal, vmax, mean)
        var = sumsq / jnp.maximum(cnt, 1) - mdelta**2
        var = jnp.where(has_avg, jnp.maximum(var, 0), jnp.nan)
        has_std = has_avg & ~all_equal & (var > 0)
        std = jnp.where(has_std, jnp.sqrt(var), jnp.nan)
    else:
        cnt, total, vmin, vmax = fused_window_partials(vals, valid)
        has_avg = (cnt > 0) & full[:, None]
        mean = jnp.where(has_avg, total / jnp.maximum(cnt, 1), jnp.nan)

        # Degenerate (all-equal) windows are resolved EXACTLY, not by float
        # luck: whether sum(x*k)/k reproduces x depends on the value and the
        # summation order (the reference's linear JS reduce and XLA's tree
        # reduction can disagree), which would turn "zero variance -> no
        # signal" (util_methods.js:44-48, the documented intent) into a coin
        # flip with std ~ 1e-13 signalling on any deviation. max==min is
        # order-independent.
        all_equal = has_avg & (vmax == vmin)
        mean = jnp.where(all_equal, vmax, mean)

        diff = jnp.where(valid, vals - mean[..., None], 0)
        var = jnp.where(has_avg, jnp.sum(diff * diff, axis=-1) / jnp.maximum(cnt, 1), jnp.nan)
        has_std = has_avg & ~all_equal & (var > 0)  # var==0 -> std undefined (the quirk)
        std = jnp.where(has_std, jnp.sqrt(var), jnp.nan)

    result, pushed = _emit_and_damp(
        cfg, mean, std, has_std, new_values, threshold, influence, last_val, fill
    )

    # shift-at-lag semantics with a GLOBAL cursor: every row writes slot g
    # this tick — active rows push; inactive rows (not yet in the registry)
    # push NaN, which preserves their all-NaN history (it starts at
    # registration, like the reference's per-key list creation). Because
    # active rows push EVERY tick and activation is permanent, a young row's
    # entries are simply the trailing slots behind the cursor and the window
    # content is identical to a per-row-cursor layout; window statistics
    # never depended on slot order. The write is ring_write's contiguous
    # dynamic_update_slice — the aliasing-friendly op — instead of a per-row
    # scatter, which XLA:CPU turns into a full ring copy even under donation
    # (measured 599 ms vs 0.6 ms per step at [8192, 3, 8640]).
    pushed_eff = jnp.where(active[:, None], pushed, jnp.nan)
    new_vals = ring_write(raw, pushed_eff, g)
    new_fill = jnp.where(active, jnp.minimum(fill + 1, L), fill)
    new_pos = (g + 1) % L
    return result, ZScoreState(new_vals, new_fill, new_pos, None)


def grow_state(state: ZScoreState, cfg: ZScoreConfig, new_capacity: int) -> Tuple[ZScoreState, ZScoreConfig]:
    S_old = state.fill.shape[0]
    if new_capacity < S_old:
        raise ValueError("cannot shrink")
    pad = new_capacity - S_old
    new_cfg = cfg._replace(capacity=new_capacity)
    agg = state.agg
    if agg is not None:
        row_pad = ((0, pad), (0, 0))
        agg = SlidingAgg(
            cnt=jnp.pad(agg.cnt, row_pad),
            vsum=jnp.pad(agg.vsum, row_pad),
            vsumsq=jnp.pad(agg.vsumsq, row_pad),
            anchor=jnp.pad(agg.anchor, row_pad),
            run_len=jnp.pad(agg.run_len, row_pad),
            last_valid=jnp.pad(agg.last_valid, row_pad, constant_values=jnp.nan),
            last_push=jnp.pad(agg.last_push, row_pad, constant_values=jnp.nan),
        )
    return ZScoreState(
        values=jnp.pad(state.values, ((0, pad), (0, 0), (0, 0)), constant_values=jnp.nan),
        fill=jnp.pad(state.fill, (0, pad)),
        pos=state.pos,  # global cursor: new rows join the shared rotation
        agg=agg,
    ), new_cfg
