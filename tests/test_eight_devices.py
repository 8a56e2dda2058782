"""Multi-device behaviour on eight forced host devices, in one subprocess so
this process keeps its own device count: the train step sharded over a
``(pod, data, model)`` mesh against the same step on one device, shard_map
pipeline parallelism, and the compressed cross-pod all-reduce."""

import json
import os
import subprocess
import sys

import pytest

# builds and runs 5 sharded train steps and two shard_map programs in a
# forced 8-device subprocess (~1 min)
pytestmark = [pytest.mark.slow, pytest.mark.multihost]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARCHS = ["glm4-9b", "llama3.2-1b", "moonshot-v1-16b-a3b", "recurrentgemma-9b", "rwkv6-1.6b"]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import sys
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

assert jax.device_count() == 8

from repro.configs import get_smoke_config
from repro.dist.meshutil import local_mesh
from repro.launch.steps import make_train_step, rules_for
from repro.models import model as M
from repro.models.config import ShapeConfig
from repro.optim import AdamWConfig, init_opt_state

out = {"train": {}}

# --- the train step on a (pod, data, model) = (2, 2, 2) mesh vs one device ---
mesh = local_mesh((2, 2, 2), ("pod", "data", "model"))
shape = ShapeConfig("t", "train", 64, 8)
init = jax.jit(M.init_params, static_argnums=0)
for arch in json.loads(sys.argv[1]):
    cfg = get_smoke_config(arch)
    # host copies: each device_put then makes buffers of its own to donate
    params = jax.device_get(init(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 65), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    # the loss of the same params and batch on one device, before the step
    # donates its inputs
    one_loss = float(jax.jit(lambda p, b: M.loss_fn(cfg, p, b)[0])(params, batch))
    built = make_train_step(cfg, mesh, rules_for(cfg), shape)
    p_shard, o_shard, b_shard = built.in_shardings
    args = (
        jax.device_put(params, p_shard),
        jax.device_put(jax.device_get(init_opt_state(AdamWConfig(), params)), o_shard),
        jax.device_put(batch, b_shard),
    )
    compiled = built.fn.lower(*args).compile()
    hlo = compiled.as_text()
    _, _, metrics = compiled(*args)
    out["train"][arch] = {
        "collectives": sum(hlo.count(op) for op in ("all-reduce", "all-gather", "reduce-scatter")),
        "sharded_loss": float(metrics["loss"]),
        "one_loss": one_loss,
    }

# --- pipeline parallelism over the pod axis ---------------------------------
from repro.dist.pipeline import gpipe_forward

d = 16
n_stages = 2
key = jax.random.PRNGKey(0)
stage_w = jax.random.normal(key, (n_stages, d, d), jnp.float32) * 0.3
x = jax.random.normal(jax.random.PRNGKey(1), (8, d), jnp.float32)

def layer_fn(w, xm):
    return jnp.tanh(xm @ w)

pp_mesh = local_mesh((2, 4), ("pod", "data"))
y_pp = gpipe_forward(layer_fn, stage_w, x, mesh=pp_mesh, axis="pod", n_micro=4)
y_ref = layer_fn(stage_w[1], layer_fn(stage_w[0], x))
out["pipeline_max_err"] = float(jnp.max(jnp.abs(y_pp - y_ref)))

# --- compressed cross-pod reduction inside shard_map -------------------------
from repro.optim.compression import cross_pod_mean_compressed, ef_init

g = jax.random.normal(jax.random.PRNGKey(2), (2, 64), jnp.float32)  # per-pod grads

def reducer(g_local, ef):
    mean, new_ef = cross_pod_mean_compressed({"g": g_local[0]}, ef, "pod")
    return mean["g"], new_ef

ef0 = ef_init({"g": g[0]})
fn = jax.shard_map(
    reducer, mesh=pp_mesh, in_specs=(P("pod"), P()), out_specs=(P(), P()),
    check_vma=False,
)
mean, _ = fn(g, ef0)
true_mean = jnp.mean(g, axis=0)
out["compressed_allreduce_err"] = float(jnp.max(jnp.abs(mean - true_mean)))

print("EIGHT_DEVICES " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, json.dumps(ARCHS)],
        capture_output=True, text=True, timeout=1200, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, f"8-device run failed:\n{proc.stdout}\n{proc.stderr}"
    line = [l for l in proc.stdout.splitlines() if l.startswith("EIGHT_DEVICES ")][-1]
    return json.loads(line[len("EIGHT_DEVICES "):])


@pytest.mark.parametrize("arch", ARCHS)
def test_sharded_train_step_matches_one_device(result, arch):
    row = result["train"][arch]
    # sharding params, state and batch over the mesh puts collectives in the step
    assert row["collectives"] > 0
    assert row["one_loss"] > 0
    assert row["sharded_loss"] == pytest.approx(row["one_loss"], rel=2e-2)


def test_pipeline_parallel_matches_reference(result):
    assert result["pipeline_max_err"] < 1e-5


def test_compressed_cross_pod_allreduce_accuracy(result):
    # int8 quantization: ~1% of the max-abs scale
    assert result["compressed_allreduce_err"] < 0.05
