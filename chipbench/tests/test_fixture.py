"""The generator's ``records`` parameters change the draws as they say."""

from collections import Counter

import numpy as np
import pytest

from chipbench.harness.fixture import ROUTE_STATS, generate_server, records_params

NAMES = tuple(f"svc{i:03d}" for i in range(8))


def _gen(records=None, n=4000, index=0):
    return generate_server("jvm00", NAMES, n, 238.0, 7, records, index)


def _calls(logs):
    return Counter(s.split(":")[-1] for s, r in zip(logs.rec_service, logs.rec_route)
                   if r == ROUTE_STATS)


def test_defaults_are_explicit_and_stable():
    a, b = _gen(), _gen(records_params(None))
    assert a.files["server.log"][0] == b.files["server.log"][0]
    with pytest.raises(ValueError):
        records_params({"kinds_of": {}})


def test_zipf_skews_the_services():
    flat, skew = _calls(_gen()), _calls(_gen({"services": {"zipf_s": 1.5}}))
    assert max(flat.values()) < 2 * min(flat.values())
    assert skew["svc000"] > 5 * skew["svc007"]


def test_active_window_moves():
    logs = _gen({"services": {"active": 2, "shift_every_s": 30, "shift_by": 2}})
    start = min(logs.rec_end_ms)
    first = {s for s, e in zip(logs.rec_service, logs.rec_end_ms) if e - start < 25_000}
    assert {s.split(":")[-1] for s in first} - {"bcottag"} == {"svc000", "svc001"}
    assert len(_calls(logs)) == len(NAMES)


def test_kinds_and_elapsed():
    logs = _gen({"kinds": {"common_timing": 1.0}, "elapsed_ms": [10, 20]})
    assert set(logs.files) == {"app_jvm00.log"}
    assert min(logs.rec_elapsed) >= 10 and max(logs.rec_elapsed) <= 20


def test_rate_steps_change_the_spacing():
    base = _gen(n=2000)
    fast = _gen({"rate": {"period_s": 0, "steps": [[0, 4.0]]}}, n=2000)
    span = lambda logs: max(logs.rec_end_ms) - min(logs.rec_end_ms)  # noqa: E731
    assert span(fast) < span(base) / 3


def test_late_lines_come_out_of_order():
    logs = _gen({"late": {"share": 0.2, "delay_ms": [5000, 20000]}})
    _blob, _ends, release = logs.files["server.log"]
    stamps = [int(line.split("] ")[1][:19].replace("-", "").replace(" ", "").replace(":", ""))
              for line in logs.files["server.log"][0].decode().splitlines()]
    assert any(b < a for a, b in zip(stamps, stamps[1:]))
    assert np.all(np.diff(release) >= 0)


def test_anomaly_scales_elapsed():
    rec = {"anomalies": [{"servers": None, "services": 1, "from_s": 0, "to_s": 1e9,
                          "elapsed_factor": 10.0}]}
    logs = _gen(rec)
    hit = [e for s, e in zip(logs.rec_service, logs.rec_elapsed) if s in ("svc000", "S:svc000")]
    rest = [e for s, e in zip(logs.rec_service, logs.rec_elapsed) if s in ("svc001", "S:svc001")]
    assert min(hit) >= 500 and max(rest) <= 1200
    limited = {"anomalies": [dict(rec["anomalies"][0], servers=1)]}
    assert max(_gen(limited, index=1).rec_elapsed) <= 1200
