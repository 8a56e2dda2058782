"""The trace reduction, on hand-made events and on traces recorded on a v5e
(``bench/record_trace.py``): five steps of a matmul chain, each followed by
20 ms of host sleep in a ``bench/host_sleep`` span; and a short window of the
chat cell's own run at two layers."""

import os

import pytest

from bench import harness
from bench.trace import WINDOW_SPAN, reduce_events, reduce_file, union_length
from conftest import ROOT

RECORDED = os.path.join(ROOT, "bench", "testdata", "small.xplane.pb")
SERVE_RECORDED = os.path.join(ROOT, "bench", "testdata", "serve.xplane.pb")


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


def test_each_chip_busy_and_the_stage_handoffs():
    """Each device's busy time, and the ops that pass activations between
    pipeline stages (as the TPU's trace names them), with the two readers of
    the four-chip cell over them."""
    ops = {
        "/device:TPU:0": [("fusion.1", 0.0, 4.0), ("collective-permute-start.1", 4.0, 4.5),
                          ("collective-permute-done.1", 4.2, 5.0)],
        "/device:TPU:1": [("fusion.1", 0.0, 2.0), ("collective-permute-done.1", 9.0, 10.0)],
    }
    r = reduce_events(ops, [], (0.0, 10.0))
    assert r["busy_s_by_device"] == [5.0, 3.0]
    assert r["busy_s"] == 4.0
    assert r["handoff_s"] == pytest.approx((1.0 + 1.0) / 2)
    out = harness.Outcome(end_to_end={}, checks=[], attempted=0, failed=0, memory_peak_bytes=0,
                          layer={"kind": "train", "trace": r}, trace=r)
    assert harness.metric_reader("stage_busy_spread.train").read(out) == pytest.approx(20.0)
    assert harness.metric_reader("handoff_share.train").read(out) == pytest.approx(10.0)
    # one chip: no spread; no hand-off op: nothing to read, not 0
    one = reduce_events({"/device:TPU:0": [("fusion.1", 0.0, 4.0)]}, [], (0.0, 10.0))
    assert one["handoff_s"] is None
    out.layer["trace"] = one
    assert harness.metric_reader("stage_busy_spread.train").read(out) is None
    assert harness.metric_reader("handoff_share.train").read(out) is None


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


def _engine_seconds(path: str) -> tuple[float, float]:
    """Seconds of the engine's ``serve/prefill`` and ``serve/decode`` spans
    inside the traced window, from the host plane."""
    from jax.profiler import ProfileData

    events = [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
              for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    lo, hi = [(s, e) for n, s, e in events if n == WINDOW_SPAN][-1]
    inside = lambda name: sum(max(min(e, hi) - max(s, lo), 0.0)  # noqa: E731
                              for n, s, e in events if n == name)
    return inside("serve/prefill"), inside("serve/decode")


def test_recorded_serve_trace_feeds_the_serve_readers():
    tr = reduce_file(SERVE_RECORDED, n_devices=1)
    prefill_s, decode_s = _engine_seconds(SERVE_RECORDED)
    assert 0 < tr["busy_s"] < tr["window_s"] and decode_s > 0
    out = harness.Outcome(
        end_to_end={}, checks=[], attempted=0, failed=0, memory_peak_bytes=0, trace=tr,
        layer={"kind": "serve", "trace": tr, "window_s": tr["window_s"],
               "prefill_span_s": prefill_s, "decode_span_s": decode_s},
    )
    idle = harness.metric_reader("device_idle_share.serve").read(out)
    host = harness.metric_reader("engine_host_share.serve").read(out)
    assert idle is not None and 0 < idle < 100
    assert host is not None and 0 < host < 100


def test_stripping_a_trace_keeps_what_the_readers_read(tmp_path):
    from jax.profiler import ProfileData

    from bench.record_trace import strip

    with open(RECORDED, "rb") as f:
        data = f.read()
    out = tmp_path / "small.xplane.pb"
    out.write_bytes(strip(data))
    assert out.stat().st_size < len(data)
    assert "/host:metadata" in {p.name for p in ProfileData.from_file(RECORDED).planes}
    assert "/host:metadata" not in {p.name for p in ProfileData.from_file(str(out)).planes}
    assert reduce_file(str(out), n_devices=1) == reduce_file(RECORDED, n_devices=1)
    assert strip(out.read_bytes()) == out.read_bytes()


def test_the_harness_traces_spans_and_no_python_calls(tmp_path, monkeypatch):
    """Python's tracer records every call and slows a host-bound loop such as
    the serving engine's several times over; the spans are what the readers
    need."""
    import glob

    import jax
    from jax.profiler import ProfileData

    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path)
    tracer = harness.Tracer("cell", on_chip=False)
    tracer.start()
    with jax.profiler.TraceAnnotation("bench/step"):
        [str(i) for i in range(100)]
    tracer.stop()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert {WINDOW_SPAN, "bench/step"} <= names
    assert not [n for n in names if n.startswith("$")]
