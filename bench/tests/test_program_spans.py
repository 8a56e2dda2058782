"""Device idle time split by the program's spans (``bench/program_spans.py``)
and the four readers over it, on hand-made events and on traces recorded here
on the CPU and on a v5e."""

import glob
import os

import pytest

from bench import harness, program_spans, trace
from conftest import ROOT

STEP = "EVOL/trainer::train_step"
READERS = {
    "idle_data_share.train": "data",
    "idle_step_host_share.train": "step_host",
    "idle_step_device_share.train": "step_device",
    "idle_loop_share.train": "loop",
}

#: one device idles in every group, the other is busy all through (0-10 s);
#: ``simulation/total`` opened before the trace and is not in it
OPS = {
    "/device:TPU:0": [("op", 1.0, 1.2), ("op", 3.5, 6.0), ("op", 6.9, 7.1)],
    "/device:TPU:1": [("op", 0.0, 10.0)],
}
SPANS = [
    ("bin/PRESTEP", "simulation/total", 0.0, 2.0),
    ("PRESTEP/data::fetch_data", "bin/PRESTEP", 0.5, 1.5),
    ("PRESTEP/data::fetch_data/put", "PRESTEP/data::fetch_data", 0.6, 0.9),
    ("bin/EVOL", "simulation/total", 2.0, 8.0),
    (STEP, "bin/EVOL", 2.1, 7.0),
    (f"{STEP}/dispatch", STEP, 2.2, 3.0),
    (f"{STEP}/wait", STEP, 3.0, 6.5),
    (f"{STEP}/fetch", STEP, 6.5, 6.8),
    ("EVOL/bench::after_step", "bin/EVOL", 7.2, 7.8),
]
WINDOW = (0.0, 10.0)


def _outcome(tr):
    return harness.Outcome(end_to_end={}, layer={"kind": "train", "chips": 2}, checks=[],
                           attempted=0, failed=0, memory_peak_bytes=0, trace=tr)


def test_each_idle_stretch_goes_to_its_innermost_span():
    r = program_spans.split_idle(OPS, SPANS, WINDOW)
    # device 0 idles 0-1, 1.2-3.5, 6.0-6.9 and 7.1-10; halved over two devices
    assert r["idle_s"] == pytest.approx({
        "data": (0.1 + 0.3 + 0.1 + 0.3) / 2,  # fetch_data and its child
        "step_host": (0.1 + 0.8 + 0.3 + 0.1) / 2,  # step, dispatch, fetch, step
        "step_device": (0.5 + 0.5) / 2,  # wait, before and after the long op
        "loop": (0.5 + 0.5 + 0.1 + 0.1 + 0.6 + 0.2 + 2.0) / 2,  # bins, bench, none
    })
    assert r["by_span"]["PRESTEP/data::fetch_data/put"] == pytest.approx(0.3 / 2)
    assert r["by_span"][f"{STEP}/dispatch"] == pytest.approx(0.8 / 2)
    assert r["by_span"]["none"] == pytest.approx(2.0 / 2)
    assert sum(r["idle_s"].values()) == pytest.approx(r["window_s"] - r["busy_s"], abs=1e-12)


def test_groups_follow_the_parent_chain():
    parents = {"a": "b", "b": "a", f"{STEP}/wait/x": f"{STEP}/wait",
               f"{STEP}/wait": STEP, "PRESTEP/r/x": "PRESTEP/r"}
    assert program_spans.group_of("a", parents) == "loop"  # a cycle ends
    assert program_spans.group_of(f"{STEP}/wait/x", parents) == "step_device"
    assert program_spans.group_of("PRESTEP/r/x", parents) == "data"
    # an enclosing routine opened before the trace still names its children
    assert program_spans.group_of("child", {"child": "PRESTEP/data::fetch_data"}) == "data"
    assert program_spans.group_of("none", parents) == "loop"


def test_readers_add_up_to_the_device_idle_share(monkeypatch, tmp_path):
    bench_spans = [("bench/traced_window", *WINDOW)]
    tr = trace.reduce_events(OPS, bench_spans, WINDOW)
    xplane = tmp_path / "host.xplane.pb"
    xplane.write_bytes(b"")
    monkeypatch.setattr(program_spans, "_newest_trace", lambda: str(xplane))
    monkeypatch.setattr(program_spans, "_read", lambda path, n: (OPS, SPANS, [WINDOW]))
    out = _outcome(tr)
    values = {name: harness.metric_reader(name).read(out) for name in READERS}
    idle = harness.metric_reader("device_idle_share.train").read(
        harness.Outcome(end_to_end={}, layer={"kind": "train", "trace": tr}, checks=[],
                        attempted=0, failed=0, memory_peak_bytes=0))
    assert values["idle_data_share.train"] == pytest.approx(100 * 0.4 / 10)
    assert sum(values.values()) == pytest.approx(idle, abs=1e-9)
    # a trace whose window is not the run's is another run's: nothing to read
    other = dict(tr, window_s=tr["window_s"] + 1e-3)
    assert all(harness.metric_reader(n).read(_outcome(other)) is None for n in READERS)


def test_readers_read_nothing_without_a_trace():
    for name in READERS:
        assert harness.metric_reader(name).read(_outcome(None)) is None


def test_a_program_without_spans_reads_nothing(monkeypatch):
    """The v5e trace holds only the benchmark's own spans, as a run of a
    program that writes none would."""
    recorded = os.path.join(ROOT, "bench", "testdata", "small.xplane.pb")
    tr = trace.reduce_file(recorded, n_devices=1)
    monkeypatch.setattr(program_spans, "_newest_trace", lambda: recorded)
    out = harness.Outcome(end_to_end={}, layer={"kind": "train", "chips": 1}, checks=[],
                          attempted=0, failed=0, memory_peak_bytes=0, trace=tr)
    assert program_spans.shares(out) is None


def test_program_spans_are_read_from_a_recorded_trace(tmp_path):
    """The host plane of a trace recorded here: the program's windows with
    their ``parent`` and the benchmark's window span (no TPU plane on a CPU)."""
    import jax

    from repro.core.schedule import RunState, Scheduler
    from repro.core.timers import TimerDB

    sch = Scheduler(TimerDB())
    sch.schedule(lambda s: None, bin="PRESTEP", thorn="data", name="fetch_data")
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        sch.run(RunState(max_iterations=1))
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    device_ops, spans, windows = program_spans._read(path, 1)
    assert device_ops == {} and len(windows) == 1
    found = {name: parent for name, parent, _, _ in spans}
    assert found["PRESTEP/data::fetch_data"] == "bin/PRESTEP"
    assert found["simulation/total"] == "/"
    assert not any(name.startswith("bench/") for name in found)
    assert all(windows[0][0] <= s and e <= windows[0][1] for _, _, s, e in spans)
