"""The real model through the 1F1B pipeline: token/grad identity against the
non-pipelined ``models.model.loss_fn`` reference with the Pallas kernels
(flash attention, fused rmsnorm, rglru scan, wkv6) active inside the staged
computation.  Tier-1 runs the single-stage schedule on the default 1-device
pod mesh (the tick clock, hook wiring, and kernel dispatch are all live);
the real multi-stage ring — including an uneven restaged plan with padded
slots — runs on a forced 4-device topology in the nightly subprocess test.

seq_len is 128 everywhere: flash attention silently falls back to the
chunked reference when ``s % 128 != 0``, and the point here is the real
Pallas interpret path.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.dist.meshutil import local_mesh
from repro.dist.pipeline import PipelineStep, StagePlan
from repro.models import model as M, pipeline as MP
from repro.models.config import ArchConfig, MoESettings

SEQ = 128
BATCH = 4

_BASE = dict(
    n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=96,
    dtype="float32", norm_impl="fused", remat="none",
)


def _dense_cfg(**kw):
    return ArchConfig(
        name="pipe-dense", family="dense", attn_impl="pallas",
        **{**_BASE, "tied_embeddings": True, **kw},
    )


def _hybrid_cfg(**kw):
    return ArchConfig(
        name="pipe-hybrid", family="hybrid", attn_impl="pallas",
        block_pattern=("rglru", "attn_local", "attn_local"), window=64,
        tied_embeddings=True, **{**_BASE, **{"n_layers": 6, **kw}},
    )


def _rwkv_cfg(**kw):
    return ArchConfig(
        name="pipe-rwkv", family="ssm", block_pattern=("rwkv",),
        rwkv_head_dim=16, n_kv_heads=4, tied_embeddings=False,
        **{**{k: v for k, v in _BASE.items() if k != "n_kv_heads"}, **kw},
    )


def _check(cfg, *, n_micro=2, plan=None, tol=1e-5):
    """Pipeline loss + merged grads vs the fused non-pipelined reference."""
    n_units = MP.check_pipelineable(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    kt, kg = jax.random.split(jax.random.PRNGKey(1))
    tokens = jax.random.randint(kt, (BATCH, SEQ), 0, cfg.vocab_size)
    targets = jax.random.randint(kg, (BATCH, SEQ), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": targets}
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: M.loss_fn(cfg, p, batch)[0]
    )(params)

    mesh = local_mesh((1,), ("pod",))
    plan = plan or StagePlan.equal(range(1), n_units)
    layer_fn, first_fn, last_fn = MP.make_stage_fns(cfg)
    step = PipelineStep(
        layer_fn, None, mesh=mesh, axis="pod", n_micro=n_micro,
        first_fn=first_fn, last_fn=last_fn,
    )
    stack, first, last = MP.split_params(cfg, params)
    packed, mask = plan.pack(stack)
    loss, (pg, fg, lg) = step(
        packed, tokens, targets, stage_mask=mask,
        first_params=first, last_params=last,
    )
    grads = MP.merge_grads(cfg, plan.unpack(pg), fg, lg)

    assert abs(float(loss - ref_loss)) < tol, (float(loss), float(ref_loss))
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), grads, ref_grads
    )
    worst = max(
        jax.tree_util.tree_leaves_with_path(errs), key=lambda kv: kv[1]
    )
    assert worst[1] < tol, (
        f"max grad diff {worst[1]:.3e} at {jax.tree_util.keystr(worst[0])}"
    )


def test_dense_attn_pipeline_matches_reference():
    """Flash attention (Pallas interpret) + fused rmsnorm, tied embeddings:
    the embed table's two gradient contributions (first-stage gather,
    last-stage matmul) must re-merge to the reference grad."""
    _check(_dense_cfg())


def test_hybrid_rglru_pipeline_matches_reference():
    """One pattern period (rglru + 2x local attention) per pipeline slot."""
    _check(_hybrid_cfg(), n_micro=2)


@pytest.mark.slow
def test_rwkv6_pipeline_matches_reference():
    """wkv6 recurrence per slot, untied head (lm_head grads flow through the
    last-stage hook only).  Nightly: the chunked wkv6 vjp dominates."""
    _check(_rwkv_cfg(), tol=2e-5)


def test_check_pipelineable_rejections():
    with pytest.raises(ValueError):  # vlm family
        MP.check_pipelineable(_dense_cfg().replace(family="vlm"))
    with pytest.raises(ValueError):  # MoE aux loss not plumbed
        MP.check_pipelineable(
            _dense_cfg().replace(
                family="moe",
                moe=MoESettings(n_experts=4, top_k=2, d_expert=64),
            )
        )
    with pytest.raises(ValueError):  # pattern does not divide n_layers
        MP.check_pipelineable(_hybrid_cfg().replace(n_layers=7))
    assert MP.check_pipelineable(_hybrid_cfg()) == 2


def test_split_merge_round_trip_preserves_structure():
    cfg = _dense_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    stack, first, last = MP.split_params(cfg, params)
    assert "embed" in first and "embed" in last  # tied: table rides along
    merged = MP.merge_grads(
        cfg, stack,
        jax.tree.map(jnp.zeros_like, first),
        jax.tree.map(jnp.zeros_like, last),
    )
    ref = jax.tree.structure(params)
    assert jax.tree.structure(merged) == ref

    cfg_u = _dense_cfg(tied_embeddings=False)
    params_u = M.init_params(cfg_u, jax.random.PRNGKey(3))
    stack, first, last = MP.split_params(cfg_u, params_u)
    assert "lm_head" in last and "embed" not in last
    merged = MP.merge_grads(cfg_u, stack, first, last)
    assert jax.tree.structure(merged) == jax.tree.structure(params_u)


def test_train_launcher_pipeline_model_path():
    """--pipeline-model end to end: the launcher reports the transformer as
    the pipelined workload and the per-phase scopes get timed."""
    from repro.core.timers import TimerDB
    from repro.launch.train import TrainSettings, run_training
    from repro.timing import TimingSession

    settings = TrainSettings(
        steps=2, global_batch=4, seq_len=32, ckpt_dir=None, ckpt_mode="off",
        report_every=0, pipeline_stages=1, pipeline_micro=2,
        pipeline_model=True,
    )
    sess = TimingSession(TimerDB())
    summary = run_training(settings, session=sess)
    assert summary["iterations"] == 2
    pipe = summary["pipeline"]
    assert pipe["workload"] != "mlp"
    loss = summary["final_metrics"]["loss"]
    assert loss == loss and loss >= 0.0
    for phase in ("warmup", "steady", "cooldown"):
        assert sess.db.get(f"train/pipeline/{phase}").count == settings.steps


# ---------------------------------------------------------------------------
# Real multi-stage ring (forced 4-device topology, nightly tier)
# ---------------------------------------------------------------------------

LAUNCHER_MULTIDEVICE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from repro.configs import get_smoke_config
from repro.core.params import reset_param_registry
from repro.launch.train import TrainSettings, run_training
from repro.timing import TimingSession

cfg = get_smoke_config("llama3.2-1b").replace(n_layers=4)

def run(**kw):
    reset_param_registry()
    settings = TrainSettings(steps=2, global_batch=4, seq_len=32, ckpt_mode="off",
                             report_every=0, **kw)
    return run_training(settings, cfg=cfg, session=TimingSession())

one = run()
pipe = run(pipeline_stages=4, pipeline_model=True, pipeline_micro=2)
loss, ref = pipe["final_metrics"]["loss"], one["final_metrics"]["loss"]
assert abs(loss - ref) <= 1e-4 * abs(ref), (loss, ref)
held = pipe["state_bytes_by_device"]
assert sorted(held) == [0, 1, 2, 3], held
# the stack is split over the stages: no device holds the one-device state
assert max(held.values()) < sum(one["state_bytes_by_device"].values()), held
print("PIPELINE_LAUNCHER_MULTIDEVICE_OK")
"""


@pytest.mark.multihost
@pytest.mark.slow
def test_pipeline_launcher_spreads_state_over_stage_devices():
    """run_training's 1F1B path on 4 devices matches the one-device run and
    keeps each stage's share of the stack (and its optimizer state) on that
    stage's device instead of every device holding all of it."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.join(repo, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-c", LAUNCHER_MULTIDEVICE_SCRIPT],
        capture_output=True, text=True, timeout=900, env=env, cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr
    assert "PIPELINE_LAUNCHER_MULTIDEVICE_OK" in proc.stdout

MULTIDEVICE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import jax.numpy as jnp

from repro.dist.meshutil import local_mesh
from repro.dist.pipeline import PipelineStep, StagePlan
from repro.models import model as M, pipeline as MP
from repro.models.config import ArchConfig

cfg = ArchConfig(
    name="pipe-md", family="dense", n_layers=8, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab_size=96, dtype="float32",
    attn_impl="pallas", norm_impl="fused", tied_embeddings=False,
    remat="none",
)
n_units = MP.check_pipelineable(cfg)
mesh = local_mesh((4,), ("pod",))
params = M.init_params(cfg, jax.random.PRNGKey(0))
kt, kg = jax.random.split(jax.random.PRNGKey(1))
tokens = jax.random.randint(kt, (6, 128), 0, cfg.vocab_size)
targets = jax.random.randint(kg, (6, 128), 0, cfg.vocab_size)
batch = {"tokens": tokens, "targets": targets}
ref_loss, ref_grads = jax.value_and_grad(
    lambda p: M.loss_fn(cfg, p, batch)[0]
)(params)

layer_fn, first_fn, last_fn = MP.make_stage_fns(cfg)
step = PipelineStep(layer_fn, None, mesh=mesh, axis="pod", n_micro=3,
                    first_fn=first_fn, last_fn=last_fn)
stack, first, last = MP.split_params(cfg, params)

for plan in (
    StagePlan.equal(range(4), n_units),
    StagePlan(n_layers=n_units, weights={0: 2.0, 1: 1.0, 2: 1.0, 3: 1.0}),
):
    packed, mask = plan.pack(stack)
    loss, (pg, fg, lg) = step(packed, tokens, targets, stage_mask=mask,
                              first_params=first, last_params=last)
    grads = MP.merge_grads(cfg, plan.unpack(pg), fg, lg)
    assert abs(float(loss - ref_loss)) < 1e-5, (float(loss), float(ref_loss))
    gd = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                      grads, ref_grads)
    worst = max(jax.tree_util.tree_leaves(gd))
    assert worst < 1e-5, worst
print("PIPELINE_TRANSFORMER_MULTIDEVICE_OK")
"""


@pytest.mark.multihost
@pytest.mark.slow
def test_transformer_pipeline_on_real_devices_subprocess():
    """Grad identity across a real 4-rank ppermute ring with embed/head
    pinned to first/last stages, even and restaged-uneven stage splits."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.join(repo, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-c", MULTIDEVICE_SCRIPT],
        capture_output=True, text=True, timeout=900, env=env, cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr
    assert "PIPELINE_TRANSFORMER_MULTIDEVICE_OK" in proc.stdout
