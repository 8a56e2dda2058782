"""The trace reduction, on hand-made events and on a trace recorded on a v5e
(``bench/record_trace.py``: five steps of a matmul chain, each followed by
20 ms of host sleep in a ``bench/host_sleep`` span)."""

import os

import pytest

from bench.trace import reduce_events, reduce_file, union_length
from conftest import ROOT

RECORDED = os.path.join(ROOT, "bench", "testdata", "small.xplane.pb")


def test_union_of_overlapping_ops():
    assert union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4


def test_busy_ops_and_named_gaps():
    ops = {
        "/device:TPU:0": [("dot", 1.0, 2.0), ("add", 1.5, 2.5), ("dot", 4.0, 5.0)],
        "/device:TPU:1": [("dot", 1.0, 3.0)],
    }
    host = [("bench/traced_window", 0.0, 6.0), ("bench/EVOL", 0.5, 2.6),
            ("bench/PRESTEP", 2.6, 4.0), ("bench/OUTPUT", 5.0, 6.0)]
    r = reduce_events(ops, host, (0.0, 6.0))
    assert r["window_s"] == 6.0
    assert r["busy_s"] == pytest.approx((2.5 + 2.0) / 2)
    assert r["device_ops"][0] == ["dot", pytest.approx((2.0 + 2.0) / 2)]
    gaps = dict(r["idle_gaps"])
    # device 0 idles 0-1 (0.5 s of no span, 0.5 s in EVOL), 2.5-4 (0.1 s of
    # EVOL, then PRESTEP), 5-6 (OUTPUT); device 1 idles 0-1 (likewise), 3-6
    # (1 s of PRESTEP, 1 s of no span, 1 s of OUTPUT)
    assert gaps["bench/PRESTEP"] == pytest.approx((1.4 + 1.0) / 2)
    assert gaps["bench/OUTPUT"] == pytest.approx((1.0 + 1.0) / 2)
    assert gaps["bench/EVOL"] == pytest.approx((0.6 + 0.5) / 2)
    assert gaps["none"] == pytest.approx((0.5 + 1.5) / 2)
    assert r["busy_s"] + sum(gaps.values()) == pytest.approx(6.0)


def test_a_gap_no_span_covers_is_none():
    r = reduce_events({"d": [("op", 2.0, 3.0)]}, [], (0.0, 4.0))
    assert dict(r["idle_gaps"]) == {"none": 3.0}


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    r = reduce_file(RECORDED, n_devices=1)
    assert r["devices"] == 1
    assert 0.1 < r["window_s"] < 1.0
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = dict(r["idle_gaps"])
    # five 20 ms sleeps with the device idle, named by their span
    assert gaps["bench/host_sleep"] >= 5 * 0.02
    assert r["busy_s"] + sum(gaps.values()) == pytest.approx(r["window_s"], rel=1e-6)
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
