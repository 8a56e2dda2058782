"""The train loop's one-step lookahead: ``train_step(i)`` dispatches step i and
then waits on step i - 1, so one step stays queued on the device.

Each run goes through ``run_training`` on the glm4-9b smoke config and is
compared with a hand loop over ``make_train_step(...).fn`` on the same
``SyntheticLM`` batches, one step at a time with nothing queued: the
arithmetic is the same, so losses and parameters agree bitwise.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import TimerLogger
from repro.core.clocks import counter_values
from repro.data import SyntheticConfig, SyntheticLM
from repro.dist.meshutil import local_mesh
from repro.launch.steps import make_train_step, rules_for
from repro.launch.train import StepMetric, TrainSettings, run_training
from repro.models import model as M
from repro.models.config import ShapeConfig
from repro.monitor.promparse import parse_exposition
from repro.optim import AdamWConfig, init_opt_state
from repro.timing import TimingSession

STEPS = 4
#: the iteration after whose step the params are swapped for the initial ones
SWAP_AT = 1
OVERLAPPED = "train_steps_overlapped"


def _settings(**kw) -> TrainSettings:
    return TrainSettings(arch="glm4-9b", smoke=True, steps=STEPS, global_batch=2, seq_len=32,
                         ckpt_mode="off", report_every=0, **kw)


def _source(settings: TrainSettings) -> SyntheticLM:
    cfg = get_smoke_config(settings.arch)
    return SyntheticLM(SyntheticConfig(cfg.vocab_size, settings.seq_len, settings.global_batch,
                                       mode=settings.data_mode, seed=settings.seed), arch=cfg)


def _host(tree):
    return jax.tree.map(np.array, jax.device_get(tree))


def _hand_loop(settings: TrainSettings, swap_at: int | None = None) -> dict:
    """Step by step with ``built.fn``, waiting on each step before the next;
    with ``swap_at``, the params go back to the initial ones after that step."""
    cfg = get_smoke_config(settings.arch)
    shape = ShapeConfig("train_local", "train", settings.seq_len, settings.global_batch)
    horizon = settings.lr_total_steps or settings.steps
    built = make_train_step(cfg, local_mesh(settings.mesh_shape), rules_for(cfg), shape,
                            opt_cfg=AdamWConfig(), peak_lr=settings.peak_lr,
                            total_steps=max(horizon, 2),
                            warmup_steps=max(min(100, horizon // 10), 1))
    p_shard, o_shard, b_shard = built.in_shardings
    # a host copy: the step donates the params it is given
    init = M.init_params(cfg, jax.random.PRNGKey(settings.seed))
    opt_state = jax.device_put(init_opt_state(AdamWConfig(), init), o_shard)
    init = _host(init)
    params = jax.device_put(init, p_shard)
    source = _source(settings)
    losses = []
    for i in range(settings.steps):
        batch = {k: jax.device_put(v, b_shard[k]) for k, v in source.batch_at(i).items()}
        params, opt_state, metrics = built.fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if i == swap_at:
            params = jax.device_put(init, p_shard)
    return {"losses": losses, "params": _host(params), "metrics": _host(metrics)}


def _run(settings: TrainSettings, swap_at: int | None = None) -> dict:
    """``run_training`` with a routine in EVOL after ``train_step`` that
    records what each iteration sees (and, with ``swap_at``, puts the
    initial params back after that iteration's step)."""
    seen = []
    sess = TimingSession()
    init = {}

    def keep_init(s):
        init["params"] = _host(s["params"])

    def after_step(s):
        seen.append({
            "iteration": s.iteration,
            "loss": float(s["metrics"]["loss"]),
            "tokens": np.asarray(s["batch"]["tokens"]),
            "opt_step": int(s["opt_state"]["step"]),
            "done": s.get("metrics_done"),
            "metrics": s["metrics"],
        })
        if s.iteration == STEPS - 1:
            seen[-1]["params"] = _host(s["params"])
        if s.iteration == swap_at:
            s["params"] = jax.device_put(init["params"], s["built"].in_shardings[0])

    sess.scheduler.schedule(keep_init, bin="INITIAL", thorn="test")
    sess.scheduler.schedule(after_step, bin="EVOL", thorn="test", after=("train_step",))
    before = counter_values([OVERLAPPED])[0]
    summary = run_training(settings, session=sess)
    return {"seen": seen, "summary": summary,
            "overlapped": counter_values([OVERLAPPED])[0] - before, "before": before}


@pytest.fixture(scope="module")
def plain(tmp_path_factory):
    d = tmp_path_factory.mktemp("lookahead")
    settings = _settings(log_path=str(d / "train.jsonl"), metrics_textfile=str(d / "metrics.prom"))
    out = _run(settings)
    out["hand"] = _hand_loop(settings)
    out["settings"] = settings
    return out


def test_lookahead_matches_a_hand_loop_bitwise(plain):
    seen, hand = plain["seen"], plain["hand"]
    assert [r["loss"] for r in seen] == hand["losses"]
    jax.tree.map(np.testing.assert_array_equal, seen[-1]["params"], hand["params"])


def test_a_routine_after_the_step_sees_its_own_iteration(plain):
    """At EVOL after ``train_step`` (i): the batch, the loss and the optimizer
    state are step i's, and ``metrics_done`` holds step i - 1's host floats."""
    source = _source(plain["settings"])
    for i, r in enumerate(plain["seen"]):
        assert r["iteration"] == i
        np.testing.assert_array_equal(r["tokens"], source.batch_at(i)["tokens"])
        assert r["loss"] == plain["hand"]["losses"][i]
        assert r["opt_step"] == i + 1
        if i == 0:
            assert r["done"] is None
        else:
            step, m = r["done"]
            assert step == i - 1
            assert m["loss"] == plain["hand"]["losses"][i - 1]
            assert all(type(v) is float for v in m.values())


def test_metrics_let_go_of_the_device_once_their_step_is_done(plain):
    """Every step's metrics were fetched by the loop (the last at shutdown),
    so none holds a device array any more, and each reads its step's value."""
    for r, loss in zip(plain["seen"], plain["hand"]["losses"]):
        assert StepMetric.arrays(r["metrics"]) == []
        assert float(r["metrics"]["loss"]) == loss


def test_final_metrics_are_floats_of_the_last_step(plain):
    final = plain["summary"]["final_metrics"]
    assert set(final) == set(plain["hand"]["metrics"])
    assert all(type(v) is float for v in final.values())
    for k, v in final.items():
        assert v == float(plain["hand"]["metrics"][k]), k


def test_the_log_holds_every_step_once_under_its_own_step(plain):
    rows = TimerLogger(plain["settings"].log_path).read_all()
    assert [r["iteration"] for r in rows] == list(range(STEPS))
    assert [r["extra"]["loss"] for r in rows] == plain["hand"]["losses"]


def test_overlapped_steps_are_counted_and_exported(plain):
    assert plain["overlapped"] == STEPS - 1
    with open(plain["settings"].metrics_textfile) as f:
        exp = parse_exposition(f.read())
    exported = exp.value("repro_counter_total", channel=OVERLAPPED)
    assert exported - plain["before"] == STEPS - 1


def test_replacing_params_between_steps_takes_effect_on_the_next_step():
    settings = _settings()
    run = _run(settings, swap_at=SWAP_AT)
    hand = _hand_loop(settings, swap_at=SWAP_AT)
    assert [r["loss"] for r in run["seen"]] == hand["losses"]
    assert hand["losses"] != _hand_loop(settings)["losses"]
    jax.tree.map(np.testing.assert_array_equal, run["seen"][-1]["params"], hand["params"])
