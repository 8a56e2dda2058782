"""Step builders: the jitted train step, and the 1F1B pipeline train steps.

Each builder returns a :class:`BuiltStep` holding the step, its shardings and
the abstract shapes of its state and inputs, so a launcher can place real
arrays (or lower the step ahead of time) without building them first.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..dist.context import use_sharding
from ..dist.pipeline import PipelineStep, StagePlan
from ..dist.sharding import DEFAULT_RULES, FSDP_RULES, ShardingRules, spec_for, tree_shardings
from ..models import model as M, pipeline as MP
from ..models.config import ArchConfig, ShapeConfig
from ..optim import AdamWConfig, adamw_update, init_opt_state, opt_state_axes, warmup_cosine
from ..timing import timed

__all__ = [
    "rules_for",
    "make_train_step",
    "make_pipeline_train_step",
    "make_transformer_pipeline_train_step",
]


def rules_for(cfg: ArchConfig, overrides: dict[str, Any] | None = None) -> ShardingRules:
    rules = FSDP_RULES if cfg.sharding == "tp+fsdp" else DEFAULT_RULES
    if overrides:
        rules = rules.with_overrides(**overrides)
    return rules


# ---------------------------------------------------------------------------
# Train inputs
# ---------------------------------------------------------------------------

def _train_batch(cfg: ArchConfig, shape: ShapeConfig, mesh: Mesh, rules: ShardingRules):
    """ShapeDtypeStruct stand-ins for the train batch of ``shape``, and their
    shardings."""
    b, s = shape.global_batch, shape.seq_len
    text = s - cfg.n_vision_patches if cfg.family == "vlm" else s
    inputs = {
        "tokens": (jax.ShapeDtypeStruct((b, text), jnp.int32), ("batch", "seq")),
        "targets": (jax.ShapeDtypeStruct((b, text), jnp.int32), ("batch", "seq")),
    }
    if cfg.family == "vlm":
        inputs["patch_embeds"] = (
            jax.ShapeDtypeStruct((b, cfg.n_vision_patches, cfg.d_model), jnp.bfloat16),
            ("batch", None, None),
        )
    if cfg.family == "encdec":
        inputs["src_frames"] = (
            jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16), ("batch", "seq", None)
        )
    specs = {name: sds for name, (sds, _) in inputs.items()}
    shardings = {
        name: NamedSharding(mesh, spec_for(axes, sds.shape, mesh, rules))
        for name, (sds, axes) in inputs.items()
    }
    return specs, shardings


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

@dataclass
class BuiltStep:
    fn: Any                    # jitted function
    abstract_inputs: tuple     # positional abstract args (excluding params/opt)
    in_shardings: tuple
    out_shardings: Any
    abstract_state: dict[str, Any]  # {"params": ..., "opt_state": ...} abstract
    #: tokens consumed per invocation — launchers feed this into the "tokens"
    #: counter channel (one counter_cell bump per executed step)
    tokens_per_call: int = 0


# scope-aware decorator: nests under the caller's active scope (the
# STARTUP driver routine in launchers)
@timed("steps::make_train_step")
def make_train_step(
    cfg: ArchConfig,
    mesh: Mesh,
    rules: ShardingRules,
    shape: ShapeConfig,
    opt_cfg: AdamWConfig | None = None,
    peak_lr: float = 3e-4,
    warmup_steps: int = 100,
    total_steps: int = 10000,
) -> BuiltStep:
    """Build the jitted train step: loss, gradients and AdamW in one program.

    Params and optimizer state are donated: the step updates them in place.
    """
    p_axes = M.param_axes(cfg)
    p_abs = M.abstract_params(cfg)
    opt_cfg = opt_cfg if opt_cfg is not None else AdamWConfig()
    o_axes = opt_state_axes(opt_cfg, p_axes)
    o_abs = jax.eval_shape(lambda p: init_opt_state(opt_cfg, p), p_abs)

    p_shard = tree_shardings(p_axes, p_abs, mesh, rules)
    o_shard = tree_shardings(o_axes, o_abs, mesh, rules)
    b_abs, b_shard = _train_batch(cfg, shape, mesh, rules)

    def train_step(params, opt_state, batch):
        with use_sharding(mesh, rules):
            lr = warmup_cosine(
                opt_state["step"], peak_lr=peak_lr, warmup_steps=warmup_steps,
                total_steps=total_steps,
            )
            grad_fn = jax.value_and_grad(lambda p: M.loss_fn(cfg, p, batch), has_aux=True)
            (_, metrics), grads = grad_fn(params)
            params, opt_state, stats = adamw_update(opt_cfg, params, grads, opt_state, lr)
            metrics = dict(metrics)
            metrics.update(stats)
            metrics["lr"] = lr
        return params, opt_state, metrics

    jitted = jax.jit(
        train_step,
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, None),
        donate_argnums=(0, 1),
    )
    return BuiltStep(
        fn=jitted,
        abstract_inputs=(b_abs,),
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, None),
        abstract_state={"params": p_abs, "opt_state": o_abs},
        tokens_per_call=shape.global_batch * shape.seq_len,
    )


@dataclass
class PipelineBuiltStep(BuiltStep):
    """A :class:`BuiltStep` whose ``fn`` drives the 1F1B pipeline schedule.

    ``fn`` is a host-side callable (not an AOT-compiled executable): the 1F1B
    schedule re-packs stage parameters from the live :class:`StagePlan` every
    step — that is what makes a run-time ``restage`` take effect on the very
    next step — and, when phase timing is on, dispatches
    warmup/steady/cooldown as separately synchronized segments.  The inner
    tick runner is jitted and cached per shape signature.
    """

    stage_plan: StagePlan | None = None
    pipeline: PipelineStep | None = None
    init_params: Any = None


@timed("steps::make_pipeline_train_step")
def make_pipeline_train_step(
    mesh: Mesh,
    stage_plan: StagePlan,
    *,
    axis: str = "pod",
    width: int = 32,
    vocab_size: int,
    seq_len: int,
    global_batch: int,
    n_micro: int,
    opt_cfg: AdamWConfig | None = None,
    peak_lr: float = 3e-4,
    warmup_steps: int = 100,
    total_steps: int = 10000,
    seed: int = 0,
    phase_cb: Any = None,
) -> PipelineBuiltStep:
    """Build the pipeline-parallel (1F1B) train step over mesh axis ``axis``.

    The model is a stack of ``stage_plan.n_layers`` homogeneous residual-MLP
    layers trained to map a token's (fixed, untrained) embedding to its
    next-token embedding — the homogeneous-stage workload the 1F1B schedule
    pipelines over the ``pod`` axis.  Layers are re-packed from the live
    ``stage_plan`` every step, so a straggler-triggered ``restage`` moves the
    stage boundaries for the next step without rebuilding anything.

    ``phase_cb(name)`` (a context-manager factory) times the schedule's
    warmup / steady / cooldown phases; launchers pass ``repro.timing`` scope
    handles.
    """
    opt_cfg = opt_cfg if opt_cfg is not None else AdamWConfig()
    n_layers = stage_plan.n_layers
    key = jax.random.PRNGKey(seed)
    k_emb, k_layers = jax.random.split(key)
    # fixed featurization: embeddings are not trained, the stage stack is
    emb = jax.random.normal(k_emb, (vocab_size, width), jnp.float32)
    emb = emb / jnp.sqrt(jnp.asarray(width, jnp.float32))
    alpha = 1.0 / float(max(n_layers, 1))

    def layer_fn(w, a):
        return a + jnp.tanh(a @ w[0]) @ w[1] * alpha

    def loss_fn(y, tgt):
        return jnp.mean((y - tgt) ** 2)

    pipeline = PipelineStep(
        layer_fn, loss_fn, mesh=mesh, axis=axis, n_micro=n_micro,
        phase_cb=phase_cb,
    )

    def init_params(init_key=None):
        k = init_key if init_key is not None else k_layers
        layers = jax.random.normal(k, (n_layers, 2, width, width), jnp.float32)
        return {"layers": layers * 0.3}

    p_abs = jax.eval_shape(init_params)
    o_abs = jax.eval_shape(lambda p: init_opt_state(opt_cfg, p), p_abs)

    def train_fn(params, opt_state, batch):
        x = emb[batch["tokens"]]
        tgt = emb[batch["targets"]]
        packed, mask = stage_plan.pack(params["layers"])
        loss, packed_grads = pipeline(packed, x, tgt, stage_mask=mask)
        grads = {"layers": stage_plan.unpack(packed_grads)}
        lr = warmup_cosine(
            opt_state["step"], peak_lr=peak_lr, warmup_steps=warmup_steps,
            total_steps=total_steps,
        )
        params, opt_state, stats = adamw_update(opt_cfg, params, grads, opt_state, lr)
        metrics = {"loss": loss, "lr": lr}
        metrics.update(stats)
        return params, opt_state, metrics

    replicated = NamedSharding(mesh, P())
    b_abs = {
        "tokens": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32),
        "targets": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32),
    }
    b_shard = {name: replicated for name in b_abs}
    return PipelineBuiltStep(
        fn=train_fn,
        abstract_inputs=(b_abs,),
        in_shardings=(None, None, b_shard),
        out_shardings=None,
        abstract_state={"params": p_abs, "opt_state": o_abs},
        tokens_per_call=global_batch * seq_len,
        stage_plan=stage_plan,
        pipeline=pipeline,
        init_params=init_params,
    )


@timed("steps::make_transformer_pipeline_train_step")
def make_transformer_pipeline_train_step(
    cfg: ArchConfig,
    mesh: Mesh,
    stage_plan: StagePlan,
    *,
    axis: str = "pod",
    seq_len: int,
    global_batch: int,
    n_micro: int,
    rules: ShardingRules | None = None,
    opt_cfg: AdamWConfig | None = None,
    peak_lr: float = 3e-4,
    warmup_steps: int = 100,
    total_steps: int = 10000,
    seed: int = 0,
    phase_cb: Any = None,
) -> PipelineBuiltStep:
    """Build a 1F1B train step over the *real* transformer stack of ``cfg``.

    One pipeline slot runs one block-pattern period of the model
    (``models.pipeline``): the scanned stack is re-packed from the live
    ``stage_plan`` every step (a run-time ``restage`` moves stage boundaries
    on the next step), the token embedding is pinned to stage 0 and the
    final-norm + LM head + CE loss to the last stage via the schedule's
    ``first_fn``/``last_fn`` hooks, and the Pallas kernels selected by
    ``cfg.attn_impl``/``cfg.norm_impl`` dispatch inside the staged
    computation.  Stage-parameter specs compose the pipeline axis with the
    config's TP/FSDP rules (``models.pipeline.stage_param_specs``).
    """
    opt_cfg = opt_cfg if opt_cfg is not None else AdamWConfig()
    rules = rules if rules is not None else rules_for(cfg)
    n_units = MP.check_pipelineable(cfg)
    if stage_plan.n_layers != n_units:
        raise ValueError(
            f"stage_plan covers {stage_plan.n_layers} units but {cfg.name} "
            f"has {n_units} pattern periods ({cfg.n_layers} layers / "
            f"{len(cfg.block_pattern)}-block pattern)"
        )
    layer_fn, first_fn, last_fn = MP.make_stage_fns(cfg)
    stage_spec = MP.stage_param_specs(cfg, mesh, rules, axis)
    pipeline = PipelineStep(
        layer_fn, None, mesh=mesh, axis=axis, n_micro=n_micro,
        first_fn=first_fn, last_fn=last_fn, phase_cb=phase_cb,
        stage_spec=stage_spec,
    )

    def init_params(init_key=None):
        k = init_key if init_key is not None else jax.random.PRNGKey(seed)
        return M.init_params(cfg, k)

    p_abs = M.abstract_params(cfg)
    o_abs = jax.eval_shape(lambda p: init_opt_state(opt_cfg, p), p_abs)

    # state placement: the scanned stack (and its optimizer state) is split
    # over the pipeline axis along its unit dimension, so each device keeps
    # about its own stage's share; embed, norm and head leaves are replicated
    replicated = NamedSharding(mesh, P())
    stage_split = NamedSharding(mesh, P(axis))
    n_stages = mesh.shape[axis]

    def placement(path, sds):
        in_stack = any(getattr(k, "key", None) == "scan" for k in path)
        return stage_split if in_stack and sds.shape[0] % n_stages == 0 else replicated

    p_shard = jax.tree_util.tree_map_with_path(placement, p_abs)
    o_shard = jax.tree_util.tree_map_with_path(placement, o_abs)

    @functools.partial(
        jax.jit, out_shardings=(p_shard, o_shard, None), donate_argnums=(0, 1)
    )
    def apply_update(params, opt_state, grads):
        lr = warmup_cosine(
            opt_state["step"], peak_lr=peak_lr, warmup_steps=warmup_steps,
            total_steps=total_steps,
        )
        params, opt_state, stats = adamw_update(opt_cfg, params, grads, opt_state, lr)
        return params, opt_state, {"lr": lr, **stats}

    def train_fn(params, opt_state, batch):
        stack, first, last = MP.split_params(cfg, params)
        packed, mask = stage_plan.pack(stack)
        loss, (packed_grads, first_grads, last_grads) = pipeline(
            packed, batch["tokens"], batch["targets"], stage_mask=mask,
            first_params=first, last_params=last,
        )
        grads = MP.merge_grads(
            cfg, stage_plan.unpack(packed_grads), first_grads, last_grads
        )
        params, opt_state, stats = apply_update(params, opt_state, grads)
        return params, opt_state, {"loss": loss, **stats}

    b_abs = {
        "tokens": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32),
        "targets": jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32),
    }
    b_shard = {name: replicated for name in b_abs}
    return PipelineBuiltStep(
        fn=train_fn,
        abstract_inputs=(b_abs,),
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=None,
        abstract_state={"params": p_abs, "opt_state": o_abs},
        tokens_per_call=global_batch * seq_len,
        stage_plan=stage_plan,
        pipeline=pipeline,
        init_params=init_params,
    )
