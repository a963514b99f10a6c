"""The vectorised z-score reference (``ZScoreLists``) against ``GoldenZScore``,
step by step, and its cost at the size of the upstream's fleet."""

import math
import time

import numpy as np
import pytest

from chipbench.harness.reference import GoldenZScore, ZScoreLists, js_to_fixed, wire_tie

METRICS = ("average", "per75", "per95")


def _start(case: str, rng: np.random.Generator, keys: int, lag: int) -> np.ndarray:
    """Starting lists [keys, 3, m] for a case."""
    x = rng.normal(620.0, 8.0, (keys, 3, lag))
    if case == "nan":
        x[rng.random(x.shape) < 0.2] = np.nan
    elif case == "zero_variance":
        x[0] = np.nan  # key 0: one valid value per list, a variance of 0
        x[0, :, lag // 2] = 611.5
        x[1] = 600.0  # key 1: every value equal
    elif case == "min_eq_max":
        # 0.1 summed is not 0.1 times the count: the variance comes out above
        # 0 and only the min == max rule leaves the list without bands
        x[0] = 0.1
        x[1, :, ::2] = np.nan
        x[1, :, 1::2] = 612.3
    elif case == "fill":
        x = x[:, :, : lag - 3]  # lists that fill during the steps
    return x


def _new(case: str, rng: np.random.Generator, keys: int, step: int) -> np.ndarray:
    v = rng.normal(620.0, 8.0, (keys, 3))
    if case == "nan":
        v[rng.random(v.shape) < 0.2] = np.nan
    elif case == "signal":
        if step % 3 == 1:
            v[: keys // 2] += 400.0  # far beyond thr x std: signals, damped pushes
    elif case == "min_eq_max":
        v[0] = 0.1
    return v


def _close(a: float, b: float) -> bool:
    """Equal, or both NaN; float64 sums in another order differ in the last digits."""
    if math.isnan(a) or math.isnan(b):
        return math.isnan(a) and math.isnan(b)
    return abs(a - b) <= 1e-12 * max(abs(a), abs(b), 1.0)


@pytest.mark.parametrize("case,lag,threshold,influence,keys,steps", [
    ("nan", 24, 3.0, 0.1, 6, 40),
    ("zero_variance", 16, 3.0, 0.1, 4, 20),
    ("min_eq_max", 16, 3.0, 0.1, 4, 20),
    ("signal", 20, 5.0, 0.1, 6, 30),
    ("fill", 12, 3.0, 0.1, 4, 20),
    ("lag360", 360, 20.0, 0.1, 5, 12),
    ("lag8640", 8640, 15.0, 0.0, 3, 6),
])
def test_steps_equal_golden(case, lag, threshold, influence, keys, steps):
    rng = np.random.default_rng([ord(c) for c in case])
    start = _start(case, rng, keys, lag)
    golden = GoldenZScore(lag, threshold, influence)
    for k in range(keys):
        golden.lists[k] = {m: start[k, i].tolist() for i, m in enumerate(METRICS)}
    lists = ZScoreLists(keys, lag, threshold, influence, start)
    signals = bands = 0
    for step in range(steps):
        new = _new(case, rng, keys, step)
        rows = [k for k in range(keys) if (k + step) % 4 or step < 2]  # some keys skip a tick
        avg, lb, ub, sig, slack = lists.step(rows, new[rows])
        assert not slack.any()  # no uncertainty given
        for j, k in enumerate(rows):
            want = golden.step(k, *new[k])
            for i, m in enumerate(METRICS):
                w = want[m]
                assert sig[j, i] == w["signal"], (step, k, m)
                for part, got in (("avg", avg), ("lb", lb), ("ub", ub)):
                    assert _close(float(got[j, i]), w[part]), (step, k, m, part, got[j, i], w[part])
                signals += abs(w["signal"])
                bands += not math.isnan(w["lb"])
    assert bands > 0
    if case == "signal":
        assert signals > 0


def test_zero_variance_and_equal_lists_have_no_bands():
    """Key 0's lists hold one valid value, key 1's are all 0.1: no bands, no
    signal, and the mean is that value."""
    start = np.full((2, 3, 8), np.nan)
    start[0, :, 3] = 611.5
    start[1] = 0.1
    lists = ZScoreLists(2, 8, 3.0, 0.1, start)
    avg, lb, ub, sig, _slack = lists.step([0, 1], [[700.0] * 3, [900.0] * 3])
    assert (avg[0] == 611.5).all() and (avg[1] == 0.1).all()
    assert np.isnan(lb).all() and np.isnan(ub).all() and (sig == 0).all()


def test_influence_uses_the_last_value():
    """A signal pushes influence x new + (1 - influence) x the list's last value."""
    start = np.tile(np.arange(10, dtype=np.float64), (1, 3, 1))
    lists = ZScoreLists(1, 10, 1.0, 0.1, start)
    _avg, _lb, _ub, sig, _slack = lists.step([0], [[100.0] * 3])
    assert (sig == 1).all()
    assert lists.values[0, 0, 0] == pytest.approx(0.1 * 100.0 + 0.9 * 9.0)


def test_wire_rounding_is_to_fixed():
    """parseFloat(x.toFixed(1)): the exact binary value decides, a tie goes up."""
    assert js_to_fixed(621.25) == 621.3
    assert js_to_fixed(0.15) == 0.1  # 0.1499999999999999944...
    assert js_to_fixed(-1.25) == -1.2
    assert math.isnan(js_to_fixed(float("nan")))


def test_fleet_size_costs_seconds():
    """380 keys, both of the upstream's lags full, 30 ticks: under 10 s."""
    rng = np.random.default_rng(7)
    keys, ticks = 380, 30
    t0 = time.perf_counter()
    for lag, threshold, influence in ((360, 20.0, 0.1), (8640, 15.0, 0.0)):
        lists = ZScoreLists(keys, lag, threshold, influence,
                            rng.normal(620.0, 8.0, (keys, 3, lag)).astype(np.float32))
        for _ in range(ticks):
            lists.step(np.arange(keys), rng.normal(620.0, 8.0, (keys, 3)))
    assert time.perf_counter() - t0 < 10.0


def test_wire_ties():
    assert wire_tie(509.45) and wire_tie(509.45 + 1e-5)  # a float32 engine may go either way
    assert not wire_tie(509.46) and not wire_tie(842.5) and not wire_tie(float("nan"))


def test_tie_slack_bounds_the_other_rounding():
    """A tie pushed one way: the lists that took the other rounding read
    within the slack while it stays in the list, and the slack is 0 after."""
    rng = np.random.default_rng(3)
    start = rng.normal(620.0, 8.0, (1, 3, 12))
    down = ZScoreLists(1, 12, 3.0, 0.1, start)
    up = ZScoreLists(1, 12, 3.0, 0.1, start)
    down.step([0], [[509.4] * 3], [[0.1] * 3])
    up.step([0], [[509.5] * 3])
    for step in range(14):
        new = rng.normal(620.0, 8.0, (1, 3))
        a_avg, _lb, a_ub, _s, slack = down.step([0], new)
        b_avg, _lb, b_ub, _s, _slack = up.step([0], new)
        if step < 12:  # 12 pushes after it, the tie has left
            assert (slack > 0).all()
            assert (np.abs(a_avg - b_avg) <= slack).all()
            assert (np.abs((a_ub - a_avg) - (b_ub - b_avg)) <= slack).all()
        else:
            assert not slack.any() and np.allclose(a_ub, b_ub, rtol=1e-12)
