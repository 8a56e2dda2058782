"""Timer windows as JAX profiler spans, and the scheduler's step markers.

Each test records a CPU trace into ``tmp_path`` and reads it back with
``jax.profiler.ProfileData``: a window is a host event named by its timer
path whose ``parent`` stat is the enclosing path (``"/"`` at the top), and
each scheduler loop iteration is an ``iteration`` event with ``step_num``.
"""

import glob
import os

import jax
import pytest

from repro.core import timers as T
from repro.core.schedule import RunState, Scheduler
from repro.core.timers import ROOT_PARENT, STEP_MARKER, timer_db


class _Recording:
    def __init__(self, log_dir):
        self.log_dir = str(log_dir)
        self.events: list[dict] = []

    def __enter__(self):
        jax.profiler.start_trace(self.log_dir)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"), recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
        for plane in pd.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    stats = dict(ev.stats)
                    if "parent" in stats or "step_num" in stats:
                        self.events.append({
                            "name": ev.name, "start": ev.start_ns,
                            "end": ev.start_ns + ev.duration_ns, "stats": stats,
                        })

    def spans(self, name: str) -> list[dict]:
        return [e for e in self.events if e["name"] == name]

    def one(self, name: str) -> dict:
        (ev,) = self.spans(name)
        return ev


def _inside(child: dict, parent: dict) -> bool:
    return parent["start"] <= child["start"] and child["end"] <= parent["end"]


def test_nested_scope_handles(tmp_path):
    db = timer_db()
    outer, inner = db.scope_handle("train/step"), db.scope_handle("train/step/forward")
    with _Recording(tmp_path) as rec:
        with outer:
            with inner:
                pass
    o, i = rec.one("train/step"), rec.one("train/step/forward")
    assert o["stats"] == {"parent": ROOT_PARENT}
    assert i["stats"] == {"parent": "train/step"}
    assert _inside(i, o)


def test_db_scope(tmp_path):
    db = timer_db()
    with _Recording(tmp_path) as rec:
        with db.scope("serve"):
            with db.scope("decode"):
                pass
            with db.scope("decode"):
                pass
    s = rec.one("serve")
    decodes = rec.spans("serve/decode")
    assert s["stats"]["parent"] == ROOT_PARENT
    assert len(decodes) == 2
    assert all(d["stats"]["parent"] == "serve" and _inside(d, s) for d in decodes)


def test_start_stop_by_handle_and_name_out_of_order(tmp_path):
    db = timer_db()
    a, b = db.create("a"), db.create("b")
    with _Recording(tmp_path) as rec:
        db.start(a)
        db.start("b")
        db.stop(a)  # out of order: the paper allows overlapping windows
        db.stop("b")
        db.start("b")  # a fresh top-level window after the overlap
        db.stop(b)
    ev_a = rec.one("a")
    first, second = sorted(rec.spans("b"), key=lambda e: e["start"])
    assert ev_a["stats"]["parent"] == ROOT_PARENT
    assert first["stats"]["parent"] == "a"
    assert ev_a["start"] <= first["start"] and ev_a["end"] <= first["end"]
    assert second["stats"]["parent"] == ROOT_PARENT
    assert db.get("a").count == 1 and db.get("b").count == 2
    assert db.current_scope() == ""


def test_scheduler_iterations_are_profiler_steps(tmp_path):
    sch = Scheduler(timer_db())
    sch.schedule(lambda s: None, bin="PRESTEP", thorn="data", name="fetch")
    sch.schedule(lambda s: None, bin="EVOL", thorn="trainer", name="step")
    with _Recording(tmp_path) as rec:
        sch.run(RunState(max_iterations=2))
    steps = sorted(rec.spans(STEP_MARKER), key=lambda e: e["start"])
    assert [e["stats"]["step_num"] for e in steps] == [0, 1]
    assert "parent" not in steps[0]["stats"]
    total = rec.one("simulation/total")
    assert total["stats"]["parent"] == ROOT_PARENT
    for path, parent in (("PRESTEP/data::fetch", "bin/PRESTEP"),
                         ("EVOL/trainer::step", "bin/EVOL"),
                         ("bin/PRESTEP", "simulation/total"),
                         ("bin/EVOL", "simulation/total")):
        windows = sorted(rec.spans(path), key=lambda e: e["start"])
        # one window per iteration, inside that iteration's step marker
        assert len(windows) == 2, path
        for w, step in zip(windows, steps):
            assert w["stats"]["parent"] == parent
            assert _inside(w, step) and _inside(w, total)
    for r, b in (("PRESTEP/data::fetch", "bin/PRESTEP"), ("EVOL/trainer::step", "bin/EVOL")):
        for w, enclosing in zip(sorted(rec.spans(r), key=lambda e: e["start"]),
                                sorted(rec.spans(b), key=lambda e: e["start"])):
            assert _inside(w, enclosing)


def test_windows_that_straddle_the_trace(tmp_path):
    db = timer_db()
    before, during = db.scope_handle("before"), db.scope_handle("during")
    before.__enter__()
    with _Recording(tmp_path) as rec:
        before.__exit__(None, None, None)  # opened with no trace: records nothing
        during.__enter__()
    during.__exit__(None, None, None)  # closed after stop_trace: raises nothing
    assert not rec.spans("before") and not rec.spans("during")
    assert db.get("before").count == 1 and db.get("during").count == 1
    with _Recording(tmp_path / "again") as rec:
        with during:
            pass
    assert rec.one("during")["stats"]["parent"] == ROOT_PARENT


def test_no_span_without_a_trace(monkeypatch):
    """With no trace recording a window makes one profiler call (the enabled
    check, at its start) and creates no span object."""
    checks = []

    def not_recording():
        checks.append(1)
        return False

    class Refuse:
        def __init__(self, *a, **k):
            raise AssertionError("a span was created with no trace recording")

    monkeypatch.setattr(T, "_trace_enabled", not_recording)
    monkeypatch.setattr(T, "_TraceMe", Refuse)
    db = timer_db()
    h = db.scope_handle("x")
    with h:
        with db.scope("y"):
            pass
    db.start("x")
    db.stop("x")
    assert len(checks) == 3
    assert h.timer._span is None
    sch = Scheduler(db)
    sch.schedule(lambda s: None, bin="EVOL", thorn="t", name="r")
    checks.clear()
    sch.run(RunState(max_iterations=2))
    # simulation/total, STARTUP, INITIAL and SHUTDOWN, 2 x (5 loop bins + the
    # routine) and 2 step markers
    assert len(checks) == 1 + 3 + 2 * 6 + 2


def test_training_step_parts_under_a_trace(tmp_path):
    from repro.configs import get_smoke_config
    from repro.launch.train import TrainSettings, _flops_per_step, run_training
    from repro.timing import TimingSession

    settings = TrainSettings(arch="llama3.2-1b", smoke=True, steps=2, global_batch=2,
                             seq_len=32, ckpt_mode="off", report_every=0)
    sess = TimingSession()
    with _Recording(tmp_path) as rec:
        run_training(settings, session=sess)
    steps = sorted(rec.spans("EVOL/trainer::train_step"), key=lambda e: e["start"])
    assert len(steps) == 2
    assert all(s["stats"]["parent"] == "bin/EVOL" for s in steps)
    (shutdown,) = rec.spans("SHUTDOWN/driver::shutdown")
    # each step window dispatches its own step and waits on the one before:
    # step 0 waits on nothing, step 1 on step 0, and the shutdown drains step 1
    for part, owners in (("dispatch", steps), ("wait", [steps[1], shutdown]),
                         ("fetch", [steps[1], shutdown])):
        windows = sorted(rec.spans(f"EVOL/trainer::train_step/{part}"),
                         key=lambda e: e["start"])
        assert len(windows) == 2, part
        for w, s in zip(windows, owners):
            assert w["stats"]["parent"] == s["name"]
            assert _inside(w, s)
    # the xla_flops counter is bumped where a step's completion is seen: the
    # step window holds step 0's count, the shutdown step 1's
    cfg = get_smoke_config("llama3.2-1b")
    for timer in ("EVOL/trainer::train_step", "SHUTDOWN/driver::shutdown"):
        flops = sess.db.get(timer).read_flat()["xla_flops"]
        assert flops == pytest.approx(_flops_per_step(cfg, 32, 2)), timer


def test_flops_per_step_at_glm4_9b_train():
    """6 x (2 layers of 203.95 M + a 4096 x 18944 head) x 4096 tokens plus
    forward and backward of causal attention: 12.76 TFLOP."""
    from repro.configs import get_config
    from repro.launch.train import _flops_per_step

    cfg = get_config("glm4-9b").replace(n_layers=2, vocab_size=18944)
    assert _flops_per_step(cfg, 4096, 1) == pytest.approx(12.76e12, rel=1e-3)
