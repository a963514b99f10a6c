"""Device z-score engine vs the float64 golden oracle (reference semantics)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apmbackend_tpu.ops import zscore as dz

from golden import GoldenZScore

METRICS = ("avg", "p75", "p95")


def drive_both(series, lag, threshold, influence, capacity=4):
    """series: list of dict key->(avg, p75, p95) per tick; keys are row ids 0..capacity-1.
    Returns list of (tick, row, metric, golden, device) comparisons."""
    golden = GoldenZScore(lag, threshold, influence)
    cfg = dz.ZScoreConfig(capacity=capacity, lag=lag, dtype=jnp.float64)
    state = dz.init_state(cfg)
    thr = jnp.full(capacity, threshold, jnp.float64)
    infl = jnp.full(capacity, influence, jnp.float64)
    step = jax.jit(dz.step, static_argnums=1)

    comparisons = []
    for t, tick_vals in enumerate(series):
        new_vals = np.full((capacity, 3), np.nan)
        for row, vals in tick_vals.items():
            new_vals[row] = vals
        # golden: per-key step ONLY for keys present this tick (reference gets
        # one StatEntry per key per tick; absent key == absent entry)
        g_out = {}
        for row, vals in tick_vals.items():
            g_out[row] = golden.step("s", f"svc{row}", *vals)
        res, state_new = step(state, cfg, jnp.asarray(new_vals), thr, infl)
        # device steps ALL rows; only compare rows that got an entry
        for row in tick_vals:
            for m_i, m in enumerate(METRICS):
                comparisons.append(
                    (
                        t, row, m,
                        g_out[row][m],
                        {
                            "avg": float(res.window_avg[row, m_i]),
                            "lb": float(res.lower_bound[row, m_i]),
                            "ub": float(res.upper_bound[row, m_i]),
                            "signal": int(res.signal[row, m_i]),
                        },
                    )
                )
        # advance device state only for rows with entries: emulate by writing
        # back selected rows (the pipeline drives all rows every tick; partial
        # presence is exercised in test_partial_rows_via_pipeline_semantics).
        # The cursor is GLOBAL, so a frozen row's ring must rotate forward by
        # one slot to keep its logical window aligned with the shared cursor
        # (rotation is content-preserving: newest stays at cursor-1, the
        # about-to-be-overwritten slot stays the oldest).
        mask = np.zeros(capacity, bool)
        for row in tick_vals:
            mask[row] = True
        rotated_old = jnp.roll(state.values, 1, axis=-1)
        state = dz.ZScoreState(
            values=jnp.where(jnp.asarray(mask)[:, None, None], state_new.values, rotated_old),
            fill=jnp.where(jnp.asarray(mask), state_new.fill, state.fill),
            pos=state_new.pos,
        )
    return comparisons


def check(comparisons):
    for t, row, m, g, d in comparisons:
        for f in ("avg", "lb", "ub"):
            gv, dv = g[f], d[f]
            if math.isnan(gv):
                assert math.isnan(dv), (t, row, m, f, gv, dv)
            else:
                assert gv == pytest.approx(dv, rel=1e-9, abs=1e-12), (t, row, m, f, gv, dv)
        assert g["signal"] == d["signal"], (t, row, m, g, d)


def test_warmup_no_signals():
    lag = 5
    series = [{0: (100.0, 110.0, 120.0)} for _ in range(4)]
    comps = drive_both(series, lag, 3.0, 0.5)
    for _, _, _, g, d in comps:
        assert d["signal"] == 0 and math.isnan(d["avg"])
    check(comps)


def test_signal_and_influence_damping():
    lag = 4
    rng = np.random.RandomState(0)
    series = []
    for i in range(4):
        series.append({0: (100 + rng.rand(), 110 + rng.rand(), 120 + rng.rand())})
    # big spike: must signal +1 and damp the stored value
    series.append({0: (500.0, 600.0, 700.0)})
    # follow-ups exercise the damped history
    for i in range(6):
        series.append({0: (100 + rng.rand(), 110 + rng.rand(), 120 + rng.rand())})
    comps = drive_both(series, lag, 2.0, 0.25)
    assert any(d["signal"] == 1 for _, _, _, _, d in comps)
    check(comps)


def test_negative_signal():
    lag = 4
    series = [{0: (100.0 + i * 0.1, 100.0, 100.0 + i * 0.05)} for i in range(4)]
    series.append({0: (1.0, 100.0, 50.0)})
    comps = drive_both(series, lag, 2.0, 0.0)
    assert any(d["signal"] == -1 for _, _, _, _, d in comps)
    check(comps)


def test_zero_variance_never_signals():
    """Constant history -> std undefined -> no signal, NaN bounds (the quirk)."""
    lag = 4
    series = [{0: (100.0, 100.0, 100.0)} for _ in range(4)]
    series.append({0: (99999.0, 99999.0, 99999.0)})  # way out, but no signal
    comps = drive_both(series, lag, 2.0, 0.5)
    last = comps[-3:]
    for _, _, _, g, d in last:
        assert d["signal"] == 0
        assert not math.isnan(d["avg"])  # avg defined
        assert math.isnan(d["ub"])  # bounds undefined
    check(comps)


def test_nan_entries_skipped_in_window():
    lag = 4
    series = []
    series.append({0: (100.0, 100.5, 101.0)})
    series.append({0: (float("nan"), float("nan"), float("nan"))})  # empty window tick
    series.append({0: (102.0, 102.5, 103.0)})
    series.append({0: (101.0, 101.5, 102.0)})
    series.append({0: (300.0, 300.0, 300.0)})  # spike over NaN-holed window
    series.append({0: (101.5, 102.0, 102.5)})
    comps = drive_both(series, lag, 2.0, 0.3)
    check(comps)


def test_nan_new_value_no_signal_no_damp():
    lag = 3
    rng = np.random.RandomState(3)
    series = [{0: tuple(100 + rng.rand(3))} for _ in range(3)]
    series.append({0: (float("nan"),) * 3})
    series.append({0: tuple(100 + rng.rand(3))})
    comps = drive_both(series, lag, 2.0, 0.5)
    check(comps)


def test_all_nan_window_undefined():
    lag = 3
    series = [{0: (float("nan"),) * 3} for _ in range(3)]
    series.append({0: (100.0, 100.0, 100.0)})
    comps = drive_both(series, lag, 2.0, 0.5)
    for _, _, _, g, d in comps:
        assert d["signal"] == 0
    check(comps)


def test_multi_key_independent():
    lag = 4
    rng = np.random.RandomState(9)
    series = []
    for i in range(12):
        tick = {0: tuple(100 + rng.rand(3))}
        if i >= 3:  # key 1 appears later: shorter history
            tick[1] = tuple(200 + 10 * rng.rand(3))
        series.append(tick)
    series.append({0: (105.0, 105.0, 105.0), 1: (900.0, 900.0, 900.0)})
    comps = drive_both(series, lag, 2.0, 0.1)
    check(comps)


def test_random_fuzz_many_configs():
    rng = np.random.RandomState(1234)
    for lag, thr, infl in [(3, 1.0, 0.0), (5, 2.5, 0.9), (8, 0.5, 1.0)]:
        series = []
        for _ in range(40):
            vals = 100 + 50 * rng.rand(3)
            if rng.rand() < 0.1:
                vals = np.array([np.nan] * 3)
            if rng.rand() < 0.15:
                vals = vals * 5  # occasional spikes
            series.append({0: tuple(vals)})
        comps = drive_both(series, lag, thr, infl)
        check(comps)


def test_grow_state():
    cfg = dz.ZScoreConfig(capacity=2, lag=4, dtype=jnp.float64)
    state = dz.init_state(cfg)
    res, state = dz.step(
        state, cfg, jnp.full((2, 3), 5.0), jnp.full(2, 2.0), jnp.full(2, 0.1)
    )
    grown, gcfg = dz.grow_state(state, cfg, 8)
    assert grown.values.shape == (8, 3, 4)
    assert int(grown.fill[0]) == 1 and int(grown.fill[5]) == 0


# ---------------------------------------------------------------- robust ----

class RobustOracle:
    """Scalar float64 median/MAD oracle mirroring the classic oracle's gating
    quirks (warm-up on raw fill, NaN skip, zero spread -> no signal,
    influence damping toward the last pushed value)."""

    def __init__(self, lag, threshold, influence):
        self.lag = lag
        self.threshold = threshold
        self.influence = influence
        self.values = []  # raw pushed (may contain NaN)

    @staticmethod
    def _median(xs):
        xs = sorted(xs)
        n = len(xs)
        if n == 0:
            return float("nan")
        return (xs[(n - 1) // 2] + xs[n // 2]) / 2

    def step(self, x):
        full = len(self.values) >= self.lag
        window = self.values[-self.lag:] if full else []
        vals = [v for v in window if not math.isnan(v)]
        has_avg = full and len(vals) > 0
        med = self._median(vals) if has_avg else float("nan")
        mad = self._median([abs(v - med) for v in vals]) if has_avg else float("nan")
        has_std = has_avg and mad > 0
        spread = dz.MAD_SIGMA * mad if has_std else float("nan")
        lb = med - self.threshold * spread if has_std else float("nan")
        ub = med + self.threshold * spread if has_std else float("nan")
        signal = 0
        if has_std and not math.isnan(x) and abs(x - med) > self.threshold * spread:
            signal = 1 if x > med else -1
        pushed = x
        if signal and self.values and not math.isnan(self.values[-1]):
            pushed = self.influence * x + (1 - self.influence) * self.values[-1]
        self.values.append(pushed)
        if len(self.values) > self.lag:
            self.values = self.values[-self.lag:]
        return {"avg": med if has_avg else float("nan"), "lb": lb, "ub": ub, "signal": signal}


def drive_robust(series, lag, threshold, influence, capacity=2):
    cfg = dz.ZScoreConfig(capacity=capacity, lag=lag, dtype=jnp.float64, robust=True)
    state = dz.init_state(cfg)
    thr = jnp.full(capacity, threshold, jnp.float64)
    infl = jnp.full(capacity, influence, jnp.float64)
    step = jax.jit(dz.step, static_argnums=1)
    out = []
    for x in series:
        nv = np.full((capacity, 3), np.nan)
        nv[0] = (x, x + 1, x + 2)
        res, state = step(state, cfg, jnp.asarray(nv), thr, infl)
        out.append(res)
    return out


@pytest.mark.parametrize("influence", [1.0, 0.2])
def test_robust_matches_oracle(influence):
    rng = np.random.RandomState(31)
    series = list(200 + 30 * rng.rand(90))
    series[40] = 5000.0
    series[41] = 4800.0
    series[60] = float("nan")
    oracle = RobustOracle(12, 3.0, influence)
    results = drive_robust(series, 12, 3.0, influence)
    for t, x in enumerate(series):
        g = oracle.step(x)
        d = results[t]
        for f, got in (("avg", float(d.window_avg[0, 0])),
                       ("lb", float(d.lower_bound[0, 0])),
                       ("ub", float(d.upper_bound[0, 0]))):
            if math.isnan(g[f]):
                assert math.isnan(got), (t, f)
            else:
                assert g[f] == pytest.approx(got, rel=1e-9, abs=1e-12), (t, f)
        assert g["signal"] == int(d.signal[0, 0]), f"t={t}"


def test_robust_zero_mad_no_signal():
    # constant window: MAD == 0 -> spread undefined -> no signal (the
    # zero-variance quirk carried over)
    series = [100.0] * 20 + [500.0]
    results = drive_robust(series, 10, 3.0, 1.0)
    assert int(results[-1].signal[0, 0]) == 0
    assert math.isnan(float(results[-1].upper_bound[0, 0]))


def test_robust_survives_outlier_contamination_classic_masked():
    """The motivating scenario: an outlier burst lands in the window. The
    classic z-score's std inflates (self-contamination) and a later genuine
    regression hides inside the widened bounds; median/MAD shrugs off the
    burst and flags the same regression."""
    rng = np.random.RandomState(7)
    lag, thr = 30, 3.0
    base = list(200 + 4 * rng.rand(60))
    burst = [4000.0, 4200.0, 3900.0]  # 3 outliers (10% of the window)
    calm = list(200 + 4 * rng.rand(20))
    probe = [260.0]  # genuine step: ~15 sigma of the clean noise, well under
    series = base + burst + calm + probe  # the burst-inflated classic bounds
    # classic path (influence=1: burst enters the window undamped)
    classic = drive_both(
        [{0: (x, x, x)} for x in series], lag, thr, influence=1.0, capacity=2
    )
    classic_last = [c for c in classic if c[0] == len(series) - 1 and c[2] == "avg"][0]
    assert classic_last[3]["signal"] == 0, "classic must be blinded by its own window"
    # robust path on the same series
    robust = drive_robust(series, lag, thr, 1.0)
    assert int(robust[-1].signal[0, 0]) == 1, "median/MAD must flag the step"


def test_robust_flows_from_config():
    from apmbackend_tpu.config import default_config
    from apmbackend_tpu.pipeline import PipelineDriver, build_engine_config

    cfg_tree = default_config()
    cfg_tree["tpuEngine"]["serviceCapacity"] = 8
    cfg_tree["tpuEngine"]["samplesPerBucket"] = 8
    cfg_tree["streamCalcZScore"]["defaults"] = [
        {"LAG": 4, "THRESHOLD": 20, "INFLUENCE": 0.1},
        {"LAG": 8, "THRESHOLD": 3, "INFLUENCE": 0.1, "ROBUST": True},
    ]
    ecfg = build_engine_config(cfg_tree, 8)
    assert [spec.robust for spec in ecfg.lags] == [False, True]
    # the engine ticks with a mixed classic/robust lag set
    from apmbackend_tpu.entries import TxEntry

    drv = PipelineDriver(cfg_tree, capacity=8)
    ts = 170_000_000_0000
    for t in range(14):
        drv.feed(TxEntry("s", "svc", f"L{t}", "A", ts - 100, float(ts), 100.0 + t, "Y"))
        ts += 10_000
    assert drv._latest_label > 0


def test_robust_window_sharding_not_supported():
    from apmbackend_tpu.parallel import make_mesh2d, make_window_sharded_step

    mesh = make_mesh2d(1, 2)
    cfg = dz.ZScoreConfig(capacity=8, lag=8, dtype=jnp.float32, robust=True)
    with pytest.raises(NotImplementedError, match="robust"):
        make_window_sharded_step(mesh, cfg)


# ----------------------------------------------------------- bf16 ring ----

def _drive_ring(series, ring_dtype, lag=12, thr=3.0, infl=0.2, capacity=2):
    cfg = dz.ZScoreConfig(capacity=capacity, lag=lag, dtype=jnp.float32,
                          ring_dtype=ring_dtype)
    state = dz.init_state(cfg)
    step = jax.jit(dz.step, static_argnums=1)
    thr_v = jnp.full(capacity, thr, jnp.float32)
    infl_v = jnp.full(capacity, infl, jnp.float32)
    out = []
    for x in series:
        nv = np.full((capacity, 3), np.nan, np.float32)
        nv[0] = (x, x + 1, x + 2)
        res, state = step(state, cfg, jnp.asarray(nv), thr_v, infl_v)
        out.append(res)
    return out, state


def test_bf16_ring_storage_and_approx_parity():
    """bfloat16 ring: stored values are bf16 (half the HBM bytes), statistics
    accumulate in f32, and results track the f32 ring within bf16's ~0.4%
    relative error — with clear-margin signals identical."""
    rng = np.random.RandomState(17)
    series = list(200 + 20 * rng.rand(40))
    series[30] = 5000.0  # far beyond any bound perturbation
    f32_res, f32_state = _drive_ring(series, None)
    bf_res, bf_state = _drive_ring(series, jnp.bfloat16)
    assert bf_state.values.dtype == jnp.bfloat16
    assert f32_state.values.dtype == jnp.float32
    for t in range(len(series)):
        a, b = f32_res[t], bf_res[t]
        np.testing.assert_allclose(
            np.nan_to_num(np.asarray(a.window_avg)),
            np.nan_to_num(np.asarray(b.window_avg)), rtol=2e-2, atol=1e-2,
        )
        np.testing.assert_array_equal(np.asarray(a.signal), np.asarray(b.signal))


def test_bf16_ring_exact_quirks():
    # constant series: every stored bf16 value is identical -> max==min ->
    # zero-variance quirk holds EXACTLY (no float luck needed)
    series = [128.0] * 20 + [500.0]
    res, _ = _drive_ring(series, jnp.bfloat16)
    assert int(res[-1].signal[0, 0]) == 0
    assert math.isnan(float(res[-1].upper_bound[0, 0]))
    # warm-up gating unchanged
    assert all(int(r.signal[0, 0]) == 0 for r in res[:12])


def test_bf16_ring_resume_roundtrip(tmp_path):
    """npz stores the bf16 ring as f32 (exact upcast); load returns the exact
    same bf16 bits."""
    from apmbackend_tpu.config import default_config
    from apmbackend_tpu.entries import TxEntry
    from apmbackend_tpu.pipeline import PipelineDriver

    cfg_tree = default_config()
    cfg_tree["tpuEngine"]["serviceCapacity"] = 8
    cfg_tree["tpuEngine"]["samplesPerBucket"] = 8
    cfg_tree["tpuEngine"]["dtype"] = "float32"
    cfg_tree["tpuEngine"]["zscoreRingDtype"] = "bfloat16"
    cfg_tree["streamCalcZScore"]["defaults"] = [{"LAG": 4, "THRESHOLD": 3, "INFLUENCE": 0.1}]
    d1 = PipelineDriver(cfg_tree, capacity=8)
    assert d1.state.zscores[0].values.dtype == jnp.bfloat16
    ts = 170_000_000_0000
    for t in range(12):
        d1.feed(TxEntry("s", "svc", f"L{t}", "A", ts - 100, float(ts), 100.0 + 7 * t, "Y"))
        ts += 10_000
    path = str(tmp_path / "resume.npz")
    d1.save_resume(path)
    d2 = PipelineDriver(cfg_tree, capacity=8)
    assert d2.load_resume(path)
    assert d2.state.zscores[0].values.dtype == jnp.bfloat16
    a = np.asarray(d1.state.zscores[0].values.astype(jnp.float32))
    b = np.asarray(d2.state.zscores[0].values.astype(jnp.float32))
    np.testing.assert_array_equal(np.nan_to_num(a), np.nan_to_num(b))


def test_ring_dtype_config_validation():
    from apmbackend_tpu.config import default_config
    from apmbackend_tpu.pipeline import build_engine_config

    cfg_tree = default_config()
    cfg_tree["tpuEngine"]["zscoreRingDtype"] = "float16"
    with pytest.raises(ValueError, match="zscoreRingDtype"):
        build_engine_config(cfg_tree, 8)
    cfg_tree["tpuEngine"]["zscoreRingDtype"] = "float32"  # == dtype -> None
    assert build_engine_config(cfg_tree, 8).zscore_ring_dtype is None
    cfg_tree["tpuEngine"]["zscoreRingDtype"] = "bfloat16"
    assert build_engine_config(cfg_tree, 8).zscore_ring_dtype == jnp.bfloat16


def test_bf16_ring_window_sharded_matches_single_chip():
    from apmbackend_tpu.parallel import make_mesh2d, make_window_sharded_step, shard_zstate

    cfg = dz.ZScoreConfig(capacity=8, lag=8, dtype=jnp.float32, ring_dtype=jnp.bfloat16)
    state_s = dz.init_state(cfg)
    state_w = shard_zstate(dz.init_state(cfg), make_mesh2d(2, 4))
    mesh = make_mesh2d(2, 4)
    wstep = make_window_sharded_step(mesh, cfg)
    step = jax.jit(dz.step, static_argnums=1)
    rng = np.random.RandomState(5)
    thr = jnp.full(8, 2.0, jnp.float32)
    infl = jnp.full(8, 0.3, jnp.float32)
    for t in range(12):
        nv = jnp.asarray((200 + 30 * rng.rand(8, 3)).astype(np.float32))
        res_s, state_s = step(state_s, cfg, nv, thr, infl)
        res_w, state_w = wstep(state_w, nv, thr, infl)
    assert state_w.values.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.nan_to_num(np.asarray(res_s.window_avg)),
        np.nan_to_num(np.asarray(res_w.window_avg)),
    )
    np.testing.assert_array_equal(np.asarray(res_s.signal), np.asarray(res_w.signal))
    np.testing.assert_array_equal(
        np.nan_to_num(np.asarray(state_s.values.astype(jnp.float32))),
        np.nan_to_num(np.asarray(state_w.values.astype(jnp.float32))),
    )


# -------------------------------------------------------- one-pass var ----

def test_onepass_f64_guard_pins_twopass():
    """onepass_var is IGNORED in f64 parity mode: bit-identical outputs to
    the two-pass config on the same stream."""
    rng = np.random.RandomState(41)
    series = list(300 + 40 * rng.rand(60))
    series[50] = 4000.0
    outs = {}
    for onepass in (False, True):
        cfg = dz.ZScoreConfig(capacity=2, lag=12, dtype=jnp.float64, onepass_var=onepass)
        state = dz.init_state(cfg)
        step = jax.jit(dz.step, static_argnums=1)
        thr = jnp.full(2, 3.0, jnp.float64)
        infl = jnp.full(2, 0.2, jnp.float64)
        out = []
        for x in series:
            nv = np.full((2, 3), np.nan)
            nv[0] = (x, x + 1, x + 2)
            res, state = step(state, cfg, jnp.asarray(nv), thr, infl)
            out.append(np.nan_to_num(np.asarray(res.upper_bound)))
        outs[onepass] = out
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b)


def test_onepass_var_f32_matches_oracle_loose():
    """The one-pass branch itself (f32) against the float64 golden oracle:
    bounds within f32-appropriate tolerance, signals identical on
    clear-margin anomalies, including across a NaN data gap."""
    rng = np.random.RandomState(41)
    series = list(300 + 40 * rng.rand(80))
    series[40] = float("nan")  # data gap: the anchor must survive it
    series[50] = 4000.0
    golden = GoldenZScore(12, 3.0, 0.2)
    cfg = dz.ZScoreConfig(capacity=2, lag=12, dtype=jnp.float32, onepass_var=True)
    state = dz.init_state(cfg)
    step = jax.jit(dz.step, static_argnums=1)
    thr = jnp.full(2, 3.0, jnp.float32)
    infl = jnp.full(2, 0.2, jnp.float32)
    for t, x in enumerate(series):
        nv = np.full((2, 3), np.nan, np.float32)
        nv[0] = (x, x + 1, x + 2)
        g = golden.step("s", "svc", x, x + 1, x + 2)["avg"]
        res, state = step(state, cfg, jnp.asarray(nv), thr, infl)
        got = float(res.upper_bound[0, 0])
        if math.isnan(g["ub"]):
            assert math.isnan(got), t
        else:
            assert g["ub"] == pytest.approx(got, rel=5e-4), t
        assert g["signal"] == int(res.signal[0, 0]), f"t={t}"


def test_onepass_var_survives_nan_gap_at_large_magnitude():
    """Regression for the anchor=0 cancellation bug: large-magnitude values
    (~1e6) with a NaN push right before a genuine spike — the one-pass
    variance must stay sane (a zero anchor computes var as a huge negative,
    clamps to 0, and silently suppresses the signal)."""
    rng = np.random.RandomState(7)
    base = 1_000_000.0
    series = list(base + 2000 * rng.rand(30))
    series += [float("nan")]          # the gap: last pushed value becomes NaN
    series += [base + 60_000.0]       # clear spike (~30 sigma) right after
    cfg = dz.ZScoreConfig(capacity=1, lag=16, dtype=jnp.float32, onepass_var=True)
    state = dz.init_state(cfg)
    step = jax.jit(dz.step, static_argnums=1)
    thr = jnp.full(1, 3.0, jnp.float32)
    infl = jnp.full(1, 1.0, jnp.float32)
    res = None
    for x in series:
        nv = np.full((1, 3), x, np.float32)
        res, state = step(state, cfg, jnp.asarray(nv), thr, infl)
    assert int(res.signal[0, 0]) == 1, "spike after a data gap must still signal"
    assert not math.isnan(float(res.upper_bound[0, 0]))


def test_onepass_var_f32_approximates_twopass():
    """f32: one-pass bounds/avg within 1e-4 relative of two-pass; signals
    identical on clear-margin anomalies; the all-equal zero-variance quirk
    stays EXACT."""
    rng = np.random.RandomState(43)
    series = list(500 + 60 * rng.rand(60))
    series[45] = 9000.0  # unambiguous spike
    results = {}
    for onepass in (False, True):
        cfg = dz.ZScoreConfig(capacity=2, lag=16, dtype=jnp.float32, onepass_var=onepass)
        state = dz.init_state(cfg)
        step = jax.jit(dz.step, static_argnums=1)
        thr = jnp.full(2, 3.0, jnp.float32)
        infl = jnp.full(2, 0.2, jnp.float32)
        out = []
        for x in series:
            nv = np.full((2, 3), np.nan, np.float32)
            nv[0] = (x, x + 1, x + 2)
            res, state = step(state, cfg, jnp.asarray(nv), thr, infl)
            out.append(res)
        results[onepass] = out
    for t in range(len(series)):
        a, b = results[False][t], results[True][t]
        np.testing.assert_allclose(
            np.nan_to_num(np.asarray(a.window_avg)), np.nan_to_num(np.asarray(b.window_avg)),
            rtol=1e-4, atol=1e-3,
        )
        np.testing.assert_allclose(
            np.nan_to_num(np.asarray(a.upper_bound)), np.nan_to_num(np.asarray(b.upper_bound)),
            rtol=1e-3, atol=1e-2,
        )
        np.testing.assert_array_equal(np.asarray(a.signal), np.asarray(b.signal))


def test_onepass_var_all_equal_exact():
    cfg = dz.ZScoreConfig(capacity=1, lag=8, dtype=jnp.float32, onepass_var=True)
    state = dz.init_state(cfg)
    step = jax.jit(dz.step, static_argnums=1)
    thr = jnp.full(1, 1.0, jnp.float32)
    infl = jnp.full(1, 1.0, jnp.float32)
    res = None
    for x in [333.3] * 12 + [900.0]:
        nv = np.full((1, 3), x, np.float32)
        res, state = step(state, cfg, jnp.asarray(nv), thr, infl)
    assert int(res.signal[0, 0]) == 0  # zero-variance quirk held exactly
    assert math.isnan(float(res.upper_bound[0, 0]))


def test_variance_pass_config_flow():
    from apmbackend_tpu.config import default_config
    from apmbackend_tpu.pipeline import build_engine_config

    tree = default_config()
    assert build_engine_config(tree, 8).zscore_onepass  # auto
    tree["tpuEngine"]["zscoreVariancePass"] = "two"
    assert not build_engine_config(tree, 8).zscore_onepass
    tree["tpuEngine"]["zscoreVariancePass"] = "bogus"
    with pytest.raises(ValueError, match="zscoreVariancePass"):
        build_engine_config(tree, 8)


def test_onepass_window_sharding_refused():
    from apmbackend_tpu.parallel import make_mesh2d, make_window_sharded_step

    mesh = make_mesh2d(1, 2)
    cfg = dz.ZScoreConfig(capacity=8, lag=8, dtype=jnp.float32, onepass_var=True)
    with pytest.raises(NotImplementedError, match="one-pass"):
        make_window_sharded_step(mesh, cfg)


# ---------------------------------------------------------------------------
# sliding O(1) aggregates (ZScoreConfig.sliding): the production default.
# Battery strategy: drive the SAME stream through the exact two-pass mode and
# the sliding mode and demand identical signal decisions (bounds to fp
# tolerance) through every hazard the incremental path owns: NaN gaps,
# constant rows (run-length guard), outlier damping, late row activation,
# periodic rebuilds, drain-to-empty windows, large-magnitude anchoring,
# build_agg restore, and the staged three-program engine executor.
# ---------------------------------------------------------------------------


def _drive_modes(series, active_from=None, lag=6, thr=3.0, infl=0.3,
                 rebuild_every=7, capacity=None):
    """Run series through two-pass and sliding (with host-cadenced rebuilds);
    returns {mode: [ZScoreResult...]}. ``series``: list of [S, 3] float32
    (NaN allowed). ``active_from``: per-row first-active tick (None = all
    active from 0)."""
    S = series[0].shape[0] if capacity is None else capacity
    out = {}
    for mode in ("two", "sliding"):
        cfg = dz.ZScoreConfig(S, lag, jnp.float32,
                              sliding=(mode == "sliding"),
                              rebuild_every=rebuild_every)
        state = dz.init_state(cfg)
        step = jax.jit(dz.step, static_argnums=1)
        rebuild = jax.jit(dz.rebuild_agg_state, static_argnums=1)
        thr_v = jnp.full(S, thr, jnp.float32)
        infl_v = jnp.full(S, infl, jnp.float32)
        res_all = []
        since = 0
        for t, vals in enumerate(series):
            if active_from is None:
                act = jnp.ones(S, bool)
            else:
                act = jnp.asarray(np.asarray(active_from) <= t)
            r, state = step(state, cfg, jnp.asarray(vals), thr_v, infl_v, act)
            res_all.append(jax.device_get(r))
            since += 1
            if mode == "sliding" and since >= rebuild_every:
                since = 0
                state = rebuild(state, cfg)
        out[mode] = res_all
    return out


def _assert_mode_parity(out, rtol=2e-4, atol=1e-3):
    n_sig = 0
    for t, (a, b) in enumerate(zip(out["two"], out["sliding"])):
        np.testing.assert_array_equal(a.signal, b.signal, err_msg=f"tick {t}")
        n_sig += int(np.abs(a.signal).sum())
        for f in ("window_avg", "lower_bound", "upper_bound"):
            x, y = getattr(a, f), getattr(b, f)
            np.testing.assert_array_equal(np.isnan(x), np.isnan(y), err_msg=f"tick {t} {f}")
            ok = ~np.isnan(x)
            if ok.any():
                np.testing.assert_allclose(x[ok], y[ok], rtol=rtol, atol=atol,
                                           err_msg=f"tick {t} {f}")
    return n_sig


def test_sliding_matches_twopass_hazard_stream():
    """The kitchen-sink stream: noise, NaN gaps, an outlier burst (damping),
    a row that goes constant, and a late-activated row. Signals must be
    IDENTICAL to the exact two-pass mode at every tick."""
    rng = np.random.RandomState(7)
    S, T = 5, 64
    series = []
    for t in range(T):
        v = (100 + 10 * rng.randn(S, 3)).astype(np.float32)
        if t % 11 == 3:
            v[1] = np.nan  # recurring NaN gap
        if t in (30, 31):
            v[2] += 500  # outlier burst -> signals + influence damping
        if t >= 40:
            v[3] = 250.0  # goes constant: run-length guard takes over
        series.append(v)
    out = _drive_modes(series, active_from=[0, 0, 0, 0, 20])  # row 4 activates late
    n_sig = _assert_mode_parity(out)
    assert n_sig > 0, "stream must actually exercise signals"


def test_sliding_large_magnitude_anchor():
    """Fresh rows at 1e6 scale with tiny variance: the first-value re-anchor
    must keep the anchored sums tight (no E[x^2]-mean^2 blowup) AND must not
    leave a phantom (v0 - 0)^2 term behind (the re-anchor consistency bug:
    both deltas must use the post-re-anchor value)."""
    rng = np.random.RandomState(11)
    series = [(1_000_000 + 2 * rng.randn(2, 3)).astype(np.float32) for _ in range(40)]
    series[25][0] += 100  # ~50 sigma: must signal
    # semantic comparison, not per-tick signal parity: with an 8-sample
    # window the std estimate is +-30% noisy and at 1e6 magnitude the f32
    # delta quantization (ulp 0.0625 vs sigma 2) legitimately flips
    # borderline draws between modes. What the anchor bugs break is GROSS:
    # anchor 0 destroys the variance entirely (catastrophic cancellation);
    # the phantom-(v0)^2 re-anchor bug inflated std ~60% and silenced the
    # 50-sigma spike. So: spike fires in sliding mode, and the band WIDTH
    # (ub - lb = 2*thr*std) tracks two-pass within a few percent.
    out = _drive_modes(series, lag=8, thr=6.0, rebuild_every=10_000)  # no rebuild help
    spike = out["sliding"][25]
    assert int(spike.signal[0, 0]) == 1, "50-sigma spike must signal in sliding mode"
    for t in range(8, 40):
        a, b = out["two"][t], out["sliding"][t]
        wa = np.asarray(a.upper_bound) - np.asarray(a.lower_bound)
        wb = np.asarray(b.upper_bound) - np.asarray(b.lower_bound)
        ok = ~(np.isnan(wa) | np.isnan(wb))
        if ok.any():
            np.testing.assert_allclose(wb[ok], wa[ok], rtol=0.08,
                                       err_msg=f"band width diverged at tick {t}")


def test_sliding_drain_and_refill():
    """A window that drains to all-NaN and refills: cnt returns to 0, sums
    reset exactly, and the re-anchor starts clean."""
    S, lag = 1, 5
    series = []
    series += [np.full((S, 3), 77.0, np.float32) for _ in range(7)]
    series += [np.full((S, 3), np.nan, np.float32) for _ in range(lag + 2)]  # drain
    rng = np.random.RandomState(3)
    series += [(40 + rng.rand(S, 3)).astype(np.float32) for _ in range(12)]  # refill
    out = _drive_modes(series, lag=lag, rebuild_every=10_000)
    _assert_mode_parity(out)


def test_sliding_constant_then_tiny_deviation_no_signal():
    """Zero-variance quirk under sliding: after the window becomes all-equal
    (through >= lag equal pushes), a small deviation must NOT signal (std
    undefined), exactly like the reference and the two-pass guard."""
    S, lag = 1, 6
    rng = np.random.RandomState(5)
    series = [(90 + 5 * rng.randn(S, 3)).astype(np.float32) for _ in range(10)]
    series += [np.full((S, 3), 120.0, np.float32) for _ in range(lag + 3)]
    probe = np.full((S, 3), 120.4, np.float32)  # would signal if std ~ float noise
    series += [probe]
    out = _drive_modes(series, lag=lag)
    _assert_mode_parity(out)
    assert int(out["sliding"][-1].signal.sum()) == 0


def test_sliding_build_agg_restore_parity():
    """Snapshot the ring mid-stream, rebuild the aggregates via build_agg
    (the resume path), continue — emissions must match the uninterrupted
    run (restore conservatism may only delay the all-equal guard, which the
    continuation here re-establishes before it matters)."""
    rng = np.random.RandomState(13)
    S, lag = 3, 6
    series = [(50 + 6 * rng.randn(S, 3)).astype(np.float32) for _ in range(40)]
    series[33][1] += 200  # a signal after the restore point

    cfg = dz.ZScoreConfig(S, lag, jnp.float32, sliding=True, rebuild_every=10_000)
    step = jax.jit(dz.step, static_argnums=1)
    thr = jnp.full(S, 3.0, jnp.float32)
    infl = jnp.full(S, 0.3, jnp.float32)

    state = dz.init_state(cfg)
    base = []
    for vals in series:
        r, state = step(state, cfg, jnp.asarray(vals), thr, infl)
        base.append(jax.device_get(r))

    state = dz.init_state(cfg)
    for vals in series[:20]:
        r, state = step(state, cfg, jnp.asarray(vals), thr, infl)
    # restore: keep only the persisted leaves, rederive the aggregates
    state = dz.ZScoreState(
        values=state.values, fill=state.fill, pos=state.pos,
        agg=dz.build_agg(state.values, cfg, state.pos),
    )
    resumed = []
    for vals in series[20:]:
        r, state = step(state, cfg, jnp.asarray(vals), thr, infl)
        resumed.append(jax.device_get(r))
    for t, (a, b) in enumerate(zip(base[20:], resumed)):
        np.testing.assert_array_equal(a.signal, b.signal, err_msg=f"tick {20+t}")
        np.testing.assert_allclose(
            np.nan_to_num(a.upper_bound), np.nan_to_num(b.upper_bound),
            rtol=2e-4, atol=1e-3,
        )


def _restore_case(case, rng, S, lag):
    """Restored lists [S, 3, lag] (oldest first) and the pushes [T, S, 3]
    that follow them, for one case of the restore derivation's test."""
    wide = lambda *shape: rng.uniform(50.0, 5000.0, shape)  # noqa: E731
    nan = np.full((1, S, 3), np.nan)
    lists = wide(S, 3, lag)
    if case == "newest_only":
        # NaN pushes age everything out but the newest valid entry; then a
        # value far from it, which may not signal against zero variance
        pushes = np.concatenate([np.repeat(nan, lag - 1, 0), wide(2, S, 3)])
    elif case == "equal_suffix":
        k = lag // 3
        lists[..., -k:] = lists[..., -1:]
        pushes = np.concatenate([np.repeat(lists[None, ..., -1], lag - k, 0),
                                 lists[None, ..., -1] + 500.0, wide(2, S, 3)])
    elif case == "all_equal":
        lists[...] = lists[..., :1]
        pushes = np.concatenate([lists[None, ..., 0] * 3.0, wide(lag, S, 3)])
    else:  # mixed: holes in the lists and the pushes, and a spike
        lists[rng.rand(S, 3, lag) < 0.2] = np.nan
        pushes = wide(2 * lag, S, 3)
        pushes[rng.rand(2 * lag, S) < 0.2] = np.nan
        pushes[lag, 0] *= 40.0
    return lists.astype(np.float32), pushes.astype(np.float32)


@pytest.mark.parametrize("case", ["newest_only", "equal_suffix", "all_equal", "mixed"])
def test_restore_derivation_matches_golden(case):
    """The engine restored from saved lists (``restore_agg``, what
    ``engine_derive_aggs`` runs) then stepped on the sliding path gives the
    float64 upstream's bands and signals: where the valid entries left in a
    list are all equal there is no band, however the list got there. The
    ring is written at a cursor off 0, as a restored engine's usually is."""
    S, lag, thr, infl, pos = 6, 8, 3.0, 0.2, 5
    rng = np.random.RandomState(sum(map(ord, case)))
    lists, pushes = _restore_case(case, rng, S, lag)

    golden = GoldenZScore(lag, thr, infl)
    for row in range(S):
        golden.lists[("s", f"svc{row}")] = {
            m: [float(v) for v in lists[row, i]] for i, m in enumerate(METRICS)}
    cfg = dz.ZScoreConfig(S, lag, jnp.float32, sliding=True, rebuild_every=10_000)
    slots = (pos - lag + np.arange(lag)) % lag  # oldest ... newest at pos - 1
    ring = np.empty_like(lists)
    ring[..., slots] = lists
    values = jnp.asarray(ring)
    state = dz.ZScoreState(values=values, fill=jnp.full(S, lag, jnp.int32),
                           pos=jnp.asarray(pos, jnp.int32),
                           agg=jax.jit(dz.restore_agg, static_argnums=1)(values, cfg, pos))
    step = jax.jit(dz.step, static_argnums=1)
    thr_v, infl_v = jnp.full(S, thr, jnp.float32), jnp.full(S, infl, jnp.float32)
    # the running float32 sums hold squares of the largest values the list
    # has held: their rounding moves a band by up to thr x sqrt(lag x eps) of
    # that value, whatever the band's own width (a drift the periodic
    # rebuild cancels, and no rebuild runs here)
    room = thr * math.sqrt(lag * np.finfo(np.float32).eps) * float(np.nanmax(np.abs(
        np.concatenate([lists.ravel(), pushes.ravel()]))))
    banded = 0
    for t, vals in enumerate(pushes):
        res, state = step(state, cfg, jnp.asarray(vals), thr_v, infl_v)
        res = jax.device_get(res)
        for row in range(S):
            want = golden.step("s", f"svc{row}", *map(float, vals[row]))
            for i, m in enumerate(METRICS):
                g = want[m]
                got = {"avg": res.window_avg[row, i], "lb": res.lower_bound[row, i],
                       "ub": res.upper_bound[row, i]}
                where = (case, t, row, m, g, got, int(res.signal[row, i]))
                for f in ("avg", "lb", "ub"):
                    assert math.isnan(g[f]) == math.isnan(got[f]), where
                    if not math.isnan(g[f]):
                        assert abs(float(got[f]) - g[f]) <= room, where
                # a signal may differ only within that room of thr x std
                if int(res.signal[row, i]) != g["signal"]:
                    margin = abs(abs(float(vals[row, i]) - g["avg"]) - (g["ub"] - g["avg"]))
                    assert margin <= room, where
                banded += not math.isnan(g["lb"])
    assert banded > 0


def test_sliding_grow_state_continues():
    cfg = dz.ZScoreConfig(4, 5, jnp.float32, sliding=True)
    state = dz.init_state(cfg)
    step = jax.jit(dz.step, static_argnums=1)
    rng = np.random.RandomState(1)
    for _ in range(8):
        v = (10 + rng.rand(4, 3)).astype(np.float32)
        _, state = step(state, cfg, jnp.asarray(v), jnp.full(4, 3.0), jnp.full(4, 0.2))
    state, cfg2 = dz.grow_state(state, cfg, 8)
    assert state.agg.cnt.shape == (8, 3)
    act = jnp.asarray(np.array([True] * 4 + [False] * 4))
    r, state = step(state, cfg2, jnp.asarray((10 + rng.rand(8, 3)).astype(np.float32)),
                    jnp.full(8, 3.0), jnp.full(8, 0.2), act)
    assert int(np.asarray(state.agg.cnt)[4:].sum()) == 0  # inactive rows untouched
    assert math.isnan(float(np.asarray(state.agg.last_push)[5, 0]))


def test_sliding_f64_parity_mode_inert():
    cfg = dz.ZScoreConfig(2, 6, jnp.float64, sliding=True)
    assert not cfg.sliding_active
    state = dz.init_state(cfg)
    assert state.agg is None


def test_sliding_config_flow():
    from apmbackend_tpu.config import default_config
    from apmbackend_tpu.pipeline import build_engine_config

    tree = default_config()
    assert build_engine_config(tree, 8).zscore_sliding  # auto -> sliding
    tree["tpuEngine"]["zscoreVariancePass"] = "sliding"
    assert build_engine_config(tree, 8).zscore_sliding
    tree["tpuEngine"]["zscoreVariancePass"] = "one"
    cfg = build_engine_config(tree, 8)
    assert not cfg.zscore_sliding and cfg.zscore_onepass
    tree["tpuEngine"]["zscoreVariancePass"] = "two"
    cfg = build_engine_config(tree, 8)
    assert not cfg.zscore_sliding and not cfg.zscore_onepass


def test_sliding_window_sharding_refused():
    from apmbackend_tpu.parallel import make_mesh2d, make_window_sharded_step

    mesh = make_mesh2d(1, 2)
    cfg = dz.ZScoreConfig(capacity=8, lag=8, dtype=jnp.float32, sliding=True)
    with pytest.raises(NotImplementedError, match="sliding"):
        make_window_sharded_step(mesh, cfg)


def test_staged_engine_step_matches_single_program():
    """make_engine_step (three-dispatch staged executor) must be BITWISE
    identical to the single-program jitted engine_tick — same math, only the
    program boundaries differ."""
    from apmbackend_tpu.pipeline import (
        engine_init, engine_tick, make_demo_engine, make_engine_step,
    )

    cfg, _, params = make_demo_engine(8, 4, [(4, 3.0, 0.2), (6, 3.0, 0.2)])
    assert cfg.zscore_sliding
    state_a = engine_init(cfg)
    state_b = engine_init(cfg)
    staged = make_engine_step(cfg)
    mono = jax.jit(engine_tick, static_argnums=1)
    label = 170_000_000
    rng = np.random.RandomState(2)
    for i in range(10):
        label += 1
        em_a, state_a = staged(state_a, label, params)
        em_b, state_b = mono(state_b, cfg, label, params)
        for la, lb in zip(em_a.lags, em_b.lags):
            np.testing.assert_array_equal(np.asarray(la.signal), np.asarray(lb.signal))
            np.testing.assert_array_equal(
                np.nan_to_num(np.asarray(la.upper_bound)),
                np.nan_to_num(np.asarray(lb.upper_bound)),
            )
    for za, zb in zip(state_a.zscores, state_b.zscores):
        np.testing.assert_array_equal(
            np.nan_to_num(np.asarray(za.values)), np.nan_to_num(np.asarray(zb.values))
        )
        np.testing.assert_array_equal(np.asarray(za.pos), np.asarray(zb.pos))
