"""The saved day (``harness/history.py``): its draw, its checks, and the
resume file it writes, restored by the program's own ``load_resume``."""

import copy

import numpy as np
import pytest

from chipbench.harness.drive import pipeline_config, window_spec, zscore_lags
from chipbench.harness.history import (History, generate, history_params, resume_arrays,
                                       write_resume)
from chipbench.harness.spec import SpecError

INTERVAL_MS = 10_000
FIRST_LABEL = 170_486_640  # the fixture's log epoch, in 10 s intervals
LAGS = [{"LAG": 12, "THRESHOLD": 20.0, "INFLUENCE": 0.1},
        {"LAG": 40, "THRESHOLD": 15.0, "INFLUENCE": 0.0}]


class _Fleet:
    """The records of a generated fleet, as ``Fleet`` holds them: two
    servers, three engine keys each at 30, 60 and 90 records per interval
    over 20 intervals, and DB-only records that do not count."""

    def __init__(self):
        server, service, route, end = [], [], [], []
        for s in ("jvm00", "jvm01"):
            for v, per in (("S:svc000", 30), ("svc000", 60), ("S:svc001", 90)):
                for label in range(FIRST_LABEL, FIRST_LABEL + 20):
                    server += [s] * per
                    service += [v] * per
                    route += [0] * per
                    end += [label * INTERVAL_MS + 5] * per
            server.append(s)
            service.append("bcottag")
            route.append(1)
            end.append(FIRST_LABEL * INTERVAL_MS - 50 * INTERVAL_MS)  # no engine record
        self.rec_server, self.rec_service = server, service
        self.rec_route = np.asarray(route, np.int8)
        self.rec_end_ms = np.asarray(end, np.int64)

    def engine_keys(self):
        return sorted({(s, v) for s, v, r in zip(self.rec_server, self.rec_service,
                                                  self.rec_route.tolist()) if r == 0})


def _program_cfg(work, **eng):
    cfg = pipeline_config({"program": {
        "tpuEngine": dict({"serviceCapacity": 16, "samplesPerBucket": 32, "dtype": "float32"},
                          **eng),
        "streamCalcZScore": {"defaults": copy.deepcopy(LAGS)}}}, str(work))
    return cfg


def _history(cfg, seed=2**31 + 3, intervals=40, outage=36):
    fleet = _Fleet()
    params = history_params({"history": {"intervals": intervals, "outage_intervals": outage}},
                            cfg)
    return fleet, generate(fleet, fleet.engine_keys(), params, window_spec(cfg),
                           zscore_lags(cfg), None, seed)


def test_draw(tmp_path):
    cfg = _program_cfg(tmp_path)
    fleet, hist = _history(cfg)
    assert hist.series.dtype == np.float32 and hist.series.shape == (6, 3, 40)
    assert hist.last_label == FIRST_LABEL - 36
    # means of the fixture's elapsed times, uniform in 50-1200 ms
    means = hist.series.astype(np.float64).mean(axis=(0, 2))
    assert means == pytest.approx([625.0, 50 + 0.75 * 1150, 50 + 0.95 * 1150], rel=0.01)
    keys = hist.keys
    # each statistic spreads by its standard error over the key's records per
    # window: 31 intervals of 30, 60 or 90 records
    sd = _history(cfg, intervals=400)[1].series.astype(np.float64).std(axis=2)
    for key, per in ((("jvm00", "S:svc000"), 30), (("jvm01", "svc000"), 60),
                     (("jvm00", "S:svc001"), 90)):
        n = per * 31
        want = [np.sqrt((1151.0 ** 2 - 1) / 12 / n)] + [
            1151.0 * np.sqrt(q * (1 - q) / n) for q in (0.75, 0.95)]
        assert sd[keys.index(key)] == pytest.approx(want, rel=0.1)
    # the same seed draws the same day, another seed another
    assert np.array_equal(_history(cfg)[1].series, hist.series)
    assert not np.array_equal(_history(cfg, seed=5)[1].series, hist.series)
    lists = hist.lists(keys[:2], 12)
    assert lists.dtype == np.float64 and lists.shape == (2, 3, 12)
    assert np.array_equal(lists, hist.series[:2, :, -12:].astype(np.float64))
    with pytest.raises(SpecError, match="no history"):
        hist.lists([("jvm09", "svc000")], 12)


@pytest.mark.parametrize("history,eng,message", [
    ({"intervals": 39, "outage_intervals": 36}, {}, "shorter than the lag"),
    ({"intervals": 40, "outage_intervals": 35}, {}, "under window"),
    ({"intervals": 40, "outage_intervals": 36, "days": 1}, {}, "unknown"),
    ({"intervals": 40, "outage_intervals": 36}, {"checkpointMode": "delta"}, "chain"),
    ({"intervals": 40, "outage_intervals": 36},
     {"ewmaChannels": [{"channelId": -1}]}, "EWMA"),
])
def test_refused(tmp_path, history, eng, message):
    with pytest.raises(SpecError, match=message):
        history_params({"history": history}, _program_cfg(tmp_path, **eng))


def test_no_history(tmp_path):
    assert history_params({}, _program_cfg(tmp_path)) is None


def test_a_day_with_a_signal_is_refused(tmp_path):
    """A series whose value lies beyond the threshold from its list fails
    the draw's own check: the lists would not be the series."""
    from chipbench.harness.history import _assert_no_signal

    series = np.random.default_rng(1).normal(600.0, 1.0, (2, 3, 40)).astype(np.float32)
    _assert_no_signal(series, [(12, 20.0, 0.1)])
    series[1, 2, 30] = 900.0
    with pytest.raises(RuntimeError, match="signal"):
        _assert_no_signal(series, [(12, 20.0, 0.1)])


def test_program_restores_the_day(tmp_path):
    """The file restores through ``PipelineDriver.load_resume``: the registry,
    the latest bucket, each ring the series' last ``lag`` values in push
    order, and the sliding aggregates derived from them equal to the float64
    sums to float32 rounding."""
    import jax

    from apmbackend_tpu.pipeline import PipelineDriver

    cfg = _program_cfg(tmp_path)
    _fleet, hist = _history(cfg)
    path = cfg["tpuEngine"]["resumeFileFullPath"]
    write_resume(path, hist, cfg)
    drv = PipelineDriver(cfg)
    assert drv.load_resume(path)
    n = len(hist.keys)
    assert [drv.registry.key_of(i) for i in range(n)] == hist.keys
    assert drv._latest_label == hist.last_label
    for i, (lag, _t, _infl) in enumerate(zscore_lags(cfg)):
        z = drv.state.zscores[i]
        values = np.asarray(z.values)
        pos = int(np.asarray(z.pos))
        order = (pos + np.arange(lag)) % lag  # oldest first from the cursor
        assert np.array_equal(values[:n][:, :, order], hist.series[:, :, -lag:])
        assert np.isnan(values[n:]).all()
        assert (np.asarray(z.fill)[:n] == lag).all() and (np.asarray(z.fill)[n:] == 0).all()
        agg = jax.tree_util.tree_map(np.asarray, z.agg)
        want = hist.series[:, :, -lag:].astype(np.float64)
        assert (agg.cnt[:n] == lag).all()
        mean = agg.anchor[:n].astype(np.float64) + agg.vsum[:n] / lag
        var = agg.vsumsq[:n] / lag - (agg.vsum[:n].astype(np.float64) / lag) ** 2
        np.testing.assert_allclose(mean, want.mean(-1), rtol=5e-7)
        np.testing.assert_allclose(var, want.var(-1), rtol=1e-5)
        np.testing.assert_array_equal(agg.last_push[:n], hist.series[:, :, -1])


def test_snapshot_schema(tmp_path):
    """Every plane at serviceCapacity rows, empty stats, no pending rows."""
    cfg = _program_cfg(tmp_path)
    _fleet, hist = _history(cfg)
    arrays = resume_arrays(hist, cfg)
    assert arrays["counts"].shape == (16, 37) and not arrays["counts"].any()
    assert np.isnan(arrays["samples"]).all() and arrays["samples"].shape == (16, 37, 32)
    assert int(arrays["latest_bucket"]) == hist.last_label
    assert arrays["z12_values"].shape == (16, 3, 12) and int(arrays["z12_pos"]) == 0
    assert arrays["pending_tx"].size == 0 and len(arrays["registry"]) == 6
    too_many = History(hist.keys * 3, np.concatenate([hist.series] * 3), hist.last_label)
    with pytest.raises(SpecError, match="serviceCapacity"):
        resume_arrays(too_many, cfg)
