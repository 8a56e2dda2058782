"""End-to-end system tests: the scheduled training loop with adaptive
checkpointing, restart determinism (fault tolerance), serving engine, and
straggler detection."""


import jax
import numpy as np
import pytest

from repro.core.params import reset_param_registry
from repro.core.timers import reset_timer_db
from repro.launch.train import TrainSettings, run_training
from repro.serving import Request, ServeSession, ServiceLevel


def _settings(tmp_path, steps, **kw):
    base = dict(
        arch="llama3.2-1b", smoke=True, steps=steps, global_batch=2, seq_len=32,
        ckpt_dir=str(tmp_path / "ckpt"), ckpt_mode="adaptive",
        ckpt_max_fraction=0.5, ckpt_max_interval_s=1e9, report_every=0,
    )
    base.update(kw)
    return TrainSettings(**base)


def _fresh():
    reset_timer_db()
    reset_param_registry()


def test_training_loop_runs_and_profiles(tmp_path):
    summary = run_training(_settings(tmp_path, steps=6))
    assert summary["iterations"] == 6
    assert np.isfinite(summary["final_metrics"]["loss"])
    bins = summary["bin_seconds"]
    assert bins["EVOL"] > 0 and bins["STARTUP"] > 0
    assert summary["checkpoint"]["n_checkpoints"] >= 1
    # one device: the whole trained state is reported on device 0
    held = summary["state_bytes_by_device"]
    assert list(held) == [jax.devices()[0].id] and held[jax.devices()[0].id] > 0
    # the hierarchical profile: >=3-deep scope nesting with consistent
    # inclusive/exclusive seconds (simulation/total -> bin -> routine -> scope)
    def depth(row):
        return 1 + max((depth(c) for c in row["children"]), default=0)

    def check(row):
        child_sum = sum(c["inclusive_s"] for c in row["children"])
        assert child_sum <= row["inclusive_s"] + 1e-9, row["timer"]
        assert row["exclusive_s"] == pytest.approx(row["inclusive_s"] - child_sum)
        for c in row["children"]:
            check(c)

    forest = {row["timer"]: row for row in summary["timer_tree"]}
    total = forest["simulation/total"]
    assert depth(total) >= 3
    for row in summary["timer_tree"]:
        check(row)
    # the compile scope nests under the STARTUP driver routine
    startup_bin = next(c for c in total["children"] if c["timer"] == "bin/STARTUP")
    driver = next(
        c for c in startup_bin["children"] if c["timer"] == "STARTUP/driver::startup"
    )
    assert any(c["timer"] == "STARTUP/compile" for c in driver["children"])


@pytest.mark.slow
def test_loss_decreases_on_learnable_task(tmp_path):
    _fresh()
    summary = run_training(
        _settings(tmp_path, steps=60, ckpt_mode="off", peak_lr=1e-2, seq_len=64,
                  global_batch=4, data_mode="skewed")
    )
    # uniform init -> ce = ln(256) = 5.55; the Zipf unigram is learnable fast
    assert summary["final_metrics"]["ce"] < 4.9


@pytest.mark.slow
def test_restart_determinism(tmp_path):
    """Fault tolerance: kill after N steps, restore, and land on the *same*
    final loss as an uninterrupted run (bitwise-deterministic substrate)."""
    # uninterrupted 8 steps
    _fresh()
    full = run_training(_settings(tmp_path / "a", steps=8, ckpt_max_fraction=1.0,
                                  lr_total_steps=8))
    # interrupted: 4 steps, then resume to 8 from the checkpoint (same LR horizon)
    _fresh()
    run_training(_settings(tmp_path / "b", steps=4, ckpt_max_fraction=1.0,
                           lr_total_steps=8))
    _fresh()
    resumed = run_training(_settings(tmp_path / "b", steps=8, ckpt_max_fraction=1.0,
                                     lr_total_steps=8))
    assert resumed["iterations"] == 8
    np.testing.assert_allclose(
        resumed["final_metrics"]["loss"], full["final_metrics"]["loss"], rtol=1e-5
    )


@pytest.mark.slow
def test_adaptive_bound_respected_with_slow_ckpt(tmp_path):
    """With an artificially slow (synchronous) writer, AdaptCheck keeps the
    checkpoint fraction near the bound while fixed-interval blows through it."""
    _fresh()
    adaptive = run_training(_settings(
        tmp_path / "ad", steps=12, ckpt_mode="adaptive", ckpt_max_fraction=0.10,
        ckpt_synchronous=True, ckpt_delay_s=0.05,
    ))
    _fresh()
    fixed = run_training(_settings(
        tmp_path / "fx", steps=12, ckpt_mode="fixed", ckpt_every=1,
        ckpt_synchronous=True, ckpt_delay_s=0.05,
    ))
    # weak bound on a short run: early checkpoints may overshoot, but the
    # controller must suppress and end up well below the every-step baseline
    assert adaptive["checkpoint"]["n_suppressed"] > 0
    assert adaptive["checkpoint"]["n_checkpoints"] < fixed["checkpoint"]["n_checkpoints"]
    # bound adherence over long horizons is what
    # examples/amr_adaptive_checkpoint.py prints (Fig. 3 reproduction)


def test_serving_engine_completes_and_steers():
    _fresh()

    from repro.configs import get_smoke_config
    from repro.models import model as M

    cfg = get_smoke_config("llama3.2-1b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeSession(
        cfg, params, n_slots=4, max_seq=64,
        slo=ServiceLevel(target_decode_ms=1e-6),  # impossible target -> steer down
    )
    rng = np.random.default_rng(0)
    handles = [
        engine.submit(Request(rid, list(rng.integers(0, cfg.vocab_size, 16)), max_new_tokens=4))
        for rid in range(8)
    ]
    done = engine.run_until_idle()
    assert len(done) == 8
    assert all(h.done and len(h.result().tokens) == 4 for h in handles)
    assert engine.max_active < 4  # steered down due to impossible latency target
    stats = engine.stats()
    assert stats["completed"] == 8.0
    # the steering happened ON the control plane: ADAPT rows in the decision log
    shrinks = [a for a in engine.control_loop.actions if a.action == "shrink_batch"]
    assert shrinks and all(a.controller == "serving" for a in shrinks)


def test_straggler_detection():
    from repro.dist.stragglers import StragglerDetector

    hits = []
    det = StragglerDetector(n_hosts=4, window=8, threshold=1.5,
                            on_straggler=lambda r: hits.append(r))
    for _ in range(8):
        for host in range(4):
            det.observe(host, 1.0 if host != 2 else 3.0)
    report = det.check(step=8)
    assert report.stragglers == [2]
    assert hits and hits[0].stragglers == [2]
