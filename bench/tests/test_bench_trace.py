"""The reduction from a profiler trace to busy time, op time and idle gaps:
on hand-made events, and on a small trace recorded on the chip
(``bench/data/``) against an independent count of the same file."""
import glob
import os

import numpy as np
import pytest

from bench import trace as tracing

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "data")


def test_reduce_on_made_events():
    ms = 1_000_000
    data = {
        "devices": [[(0, 10 * ms, "fusion.1"), (5 * ms, 12 * ms, "fusion.2"),
                     (20 * ms, 30 * ms, "%ragged_paged_attention.3 = f32[8] custom-call()")]],
        "host": [(11 * ms, 25 * ms, "bench.step"), (12 * ms, 19 * ms, "bench.plan")],
    }
    red = tracing.reduce(data, 0.04)
    assert red["busy_s"] == pytest.approx(0.022)  # 0-12 ms and 20-30 ms
    assert red["op_s"]["fusion"] == pytest.approx(0.017)
    assert red["op_s"]["ragged_paged_attention"] == pytest.approx(0.010)
    # the 12-20 ms gap's middle (16 ms) lies under bench.plan, the
    # innermost host span there
    assert red["idle_gaps"] == [["bench.plan", pytest.approx(0.008)]]
    assert red["device_ops"][0][0] == "fusion"


def test_union_merges_overlaps():
    assert tracing.union([(0, 5, "a"), (3, 8, "b"), (10, 12, "c")]) == [[0, 8], [10, 12]]


def _recorded():
    files = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))
    if not files:
        pytest.fail("no recorded chip trace in bench/data")
    return files[0]


def test_reduce_on_recorded_chip_trace():
    data = tracing.load(_recorded())
    assert data["devices"] and all(data["devices"])
    ops = data["devices"][0]
    # an independent count: busy time by sweeping a sorted array
    a = np.array([[s, e] for s, e, _n in ops], np.int64)
    a = a[np.argsort(a[:, 0])]
    busy, end = 0, a[0, 0]
    for s, e in a:
        if e > end:
            busy += e - max(s, end)
            end = e
    window = (a[:, 1].max() - a[:, 0].min()) / 1e9
    red = tracing.reduce(data, window)
    assert red["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < red["busy_s"] <= window
    assert sum(red["op_s"].values()) == pytest.approx((a[:, 1] - a[:, 0]).sum() / 1e9)
    assert "ragged_paged_attention" in red["op_s"]
    assert red["op_s"]["ragged_paged_attention"] > 0
    # every gap longer than a host span's floor is named by what the host ran
    assert red["idle_gaps"] and all(label for label, _s in red["idle_gaps"])
    assert sum(s for _l, s in red["idle_gaps"]) <= window - red["busy_s"] + 1e-9
