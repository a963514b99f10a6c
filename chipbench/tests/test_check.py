"""Bounds on when the records the parser parks reach the device."""

import numpy as np
import pytest

from chipbench.harness.check import Expected, reference_rows
from chipbench.harness.reference import WindowSpec

SPEC = WindowSpec(30, 6, 10)
KEY = ("jvm00", "svc000")


def _expected(fed):
    # rank order: an on-time record and a parked one in bucket 100, then
    # on-time records opening buckets 106 and 107 (a tick each, after the
    # first record's own)
    labels = [100, 100, 106, 107]
    return Expected([KEY[0]] * 4, [KEY[1]] * 4, np.zeros(4, np.int8),
                    np.asarray(labels) * SPEC.interval_ms + 5, np.asarray([100, 200, 300, 400]),
                    np.arange(4), np.asarray([False, True, False, False]), np.asarray(fed))


def _edge(latest):
    return (latest - SPEC.buffer_sz - 1) * SPEC.interval_ms


def _cut_gap(counts, times, fed=(0, 0, 1, 2)):
    device = {(KEY, _edge(106)): counts[0], (KEY, _edge(107)): counts[1]}
    _rows, edges, cut_gap, _unseen = reference_rows(_expected(fed), [KEY], SPEC, device, times)
    assert edges == [_edge(100), _edge(106), _edge(107)]
    return cut_gap


@pytest.mark.parametrize("counts,times,gap", [
    ((1, 2), (0.0, 10.0, 20.0), 0),   # parked record arrives at the second tick
    ((2, 2), (0.0, 10.0, 20.0), 0),   # ... or already at the first
    ((1, 1), (0.0, 10.0, 20.0), 0),   # not due yet at either tick
    ((2, 1), (0.0, 10.0, 20.0), 1),   # arrived, then gone: the cut may not fall
    ((1, 1), (0.0, 10.0, 40.0), 1),   # due 35 s after it was parked, and missing
    ((1, 2), (0.0, 10.0, 40.0), 0),
])
def test_parked_cut_bounds(counts, times, gap):
    assert _cut_gap(counts, times) == gap
