"""Unified model API over all assigned families.

Layer stacks ``lax.scan`` over *pattern periods*: the block-pattern (e.g.
``("rglru","attn_local","attn_local")`` for the hybrid arch) forms one scanned
unit, so heterogeneous stacks still compile to a single rolled loop (small HLO,
fast compiles, natural remat boundary).  Layers that do not fill a whole period
run unrolled as the "tail".

Public surface:
    model_specs(cfg)                 -> PSpec tree (params, never allocated)
    init_params(cfg, key)            -> concrete params
    abstract_params(cfg)             -> ShapeDtypeStruct tree (no allocation)
    param_axes(cfg)                  -> logical-axes tree
    param_counts(cfg)                -> (total, active) parameter counts
    cache_specs(cfg, batch, max_seq) -> PSpec tree (decode cache)
    loss_fn(cfg, params, batch)      -> (loss, metrics)
    prefill(cfg, params, batch, cache) -> (cache, last_logits)
    decode_step(cfg, params, cache, tokens) -> (cache, logits)
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..dist.context import constrain
from ..kernels.flash_attention import ops as fa_ops
from .blocks import block_apply, block_cache_specs, block_specs
from .config import ArchConfig
from .layers import PSpec, abstract, axes_tree, is_pspec, materialize, rms_norm, rotary_embedding

__all__ = [
    "model_specs",
    "init_params",
    "abstract_params",
    "param_axes",
    "param_counts",
    "attention_layers",
    "cache_specs",
    "cache_axes",
    "init_cache",
    "abstract_cache",
    "forward",
    "loss_fn",
    "prefill",
    "decode_step",
]


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def decoder_pattern(cfg: ArchConfig) -> tuple[str, ...]:
    """Block pattern of the decoder stack (enc-dec decoders use xattn blocks)."""
    return ("xattn",) if cfg.family == "encdec" else cfg.block_pattern


def _split_stack(cfg: ArchConfig) -> tuple[int, tuple[str, ...], tuple[str, ...]]:
    """(n_scan_units, pattern, tail_kinds) for the decoder stack."""
    pattern = decoder_pattern(cfg)
    p = len(pattern)
    n_scan = cfg.n_layers // p
    tail = tuple(pattern[i % p] for i in range(n_scan * p, cfg.n_layers))
    return n_scan, pattern, tail


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def model_specs(cfg: ArchConfig) -> dict[str, Any]:
    dt = _dtype(cfg)
    d, vp = cfg.d_model, cfg.padded_vocab
    n_scan, pattern, tail = _split_stack(cfg)
    specs: dict[str, Any] = {
        "embed": {"tokens": PSpec((vp, d), ("vocab", "embed"), scale=0.02, dtype=dt)},
        "final_norm": PSpec((d,), (None,), init="ones", dtype=dt),
        "scan": tuple(block_specs(cfg, k, (n_scan,)) for k in pattern) if n_scan else None,
        "tail": tuple(block_specs(cfg, k) for k in tail),
    }
    if not cfg.tied_embeddings:
        specs["lm_head"] = PSpec((d, vp), ("embed", "vocab"), dtype=dt)
    if cfg.family == "encdec":
        n_enc = cfg.n_enc_layers
        specs["enc_scan"] = (block_specs(cfg, "attn", (n_enc,)),) if n_enc else None
        specs["enc_final_norm"] = PSpec((d,), (None,), init="ones", dtype=dt)
    return specs


def init_params(cfg: ArchConfig, key: jax.Array):
    return materialize(model_specs(cfg), key)


def abstract_params(cfg: ArchConfig):
    return abstract(model_specs(cfg))


def param_axes(cfg: ArchConfig):
    return axes_tree(model_specs(cfg))


def param_counts(cfg: ArchConfig) -> tuple[int, int]:
    """(total, active) — active scales expert weights by top_k / n_experts and
    excludes embedding/lm_head (6·N·D convention counts matmul params)."""
    import numpy as np

    specs = model_specs(cfg)
    flat = jax.tree_util.tree_flatten_with_path(specs, is_leaf=is_pspec)[0]
    total = 0
    active = 0
    for path, spec in flat:
        n = int(np.prod(spec.shape))
        total += n
        keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        if "embed" in keys or "lm_head" in keys:
            continue
        if cfg.moe is not None and "moe" in keys and any(k in ("wi", "wg", "wo") for k in keys):
            active += int(n * cfg.moe.top_k / cfg.moe.n_experts)
        else:
            active += n
    return total, active


def attention_layers(cfg: ArchConfig) -> int:
    """Full-sequence attentions in one forward of the train step: one per
    ``attn``/``attn_local`` block, two per ``xattn`` block (self and cross),
    one per encoder layer.  All of them attend over the step's sequence."""
    pattern = decoder_pattern(cfg)
    per_kind = {"attn": 1, "attn_local": 1, "xattn": 2}
    decoder = sum(per_kind.get(pattern[i % len(pattern)], 0) for i in range(cfg.n_layers))
    return decoder + (cfg.n_enc_layers if cfg.family == "encdec" else 0)


# ---------------------------------------------------------------------------
# Cache specs
# ---------------------------------------------------------------------------

def cache_specs(cfg: ArchConfig, batch: int, max_seq: int) -> dict[str, Any]:
    n_scan, pattern, tail = _split_stack(cfg)
    return {
        "pos": PSpec((batch,), ("batch",), init="zeros", dtype=jnp.int32),
        "scan": tuple(
            block_cache_specs(cfg, k, batch, max_seq, (n_scan,)) for k in pattern
        )
        if n_scan
        else None,
        "tail": tuple(block_cache_specs(cfg, k, batch, max_seq) for k in tail),
    }


def cache_axes(cfg: ArchConfig, batch: int, max_seq: int):
    return axes_tree(cache_specs(cfg, batch, max_seq))


def init_cache(cfg: ArchConfig, batch: int, max_seq: int):
    return materialize(cache_specs(cfg, batch, max_seq), jax.random.PRNGKey(0))


def abstract_cache(cfg: ArchConfig, batch: int, max_seq: int):
    return abstract(cache_specs(cfg, batch, max_seq))


# ---------------------------------------------------------------------------
# Stack application
# ---------------------------------------------------------------------------

def _unit_apply(cfg, kinds, unit_p, h, *, rope, mode, unit_cache, pos, enc_out, causal):
    new_caches = []
    aux = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(kinds):
        c = unit_cache[i] if unit_cache is not None else None
        h, nc, a = block_apply(
            cfg, kind, unit_p[i], h, rope=rope, mode=mode, cache=c, pos=pos,
            enc_out=enc_out, causal=causal,
        )
        new_caches.append(nc)
        aux = aux + a
    return h, tuple(new_caches), aux


def _remat_wrap(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        # matmul outputs without batch dims, and the flash kernel's output and
        # logsumexp, so the backward does not run the kernel's forward again
        policies = jax.checkpoint_policies
        policy = policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable,
            policies.save_only_these_names(*fa_ops.RESIDUAL_NAMES),
        )
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # "full": save nothing


def _apply_stack(
    cfg,
    params,
    h,
    *,
    kinds_pattern,
    scan_key,
    tail_key,
    rope,
    mode,
    caches=None,
    pos=None,
    enc_out=None,
    causal=True,
):
    """Run the scanned units then the tail. Returns (h, new_caches, aux)."""
    aux = jnp.zeros((), jnp.float32)
    p_scan = params.get(scan_key)
    c_scan = caches.get(scan_key) if caches is not None else None
    new_scan = None
    if p_scan is not None:
        def body(carry, xs):
            h, aux = carry
            unit_p, unit_c = xs
            h, new_c, a = _unit_apply(
                cfg, kinds_pattern, unit_p, h, rope=rope, mode=mode,
                unit_cache=unit_c, pos=pos, enc_out=enc_out, causal=causal,
            )
            return (h, aux + a), new_c

        if mode == "train":
            body = _remat_wrap(cfg, body)
        (h, aux), new_scan = jax.lax.scan(body, (h, aux), (p_scan, c_scan))
    new_tail = []
    tail_p = params.get(tail_key, ())
    for i, bp in enumerate(tail_p):
        kind = kinds_pattern[i % len(kinds_pattern)]
        c = caches[tail_key][i] if caches is not None else None
        h, nc, a = block_apply(
            cfg, kind, bp, h, rope=rope, mode=mode, cache=c, pos=pos,
            enc_out=enc_out, causal=causal,
        )
        new_tail.append(nc)
        aux = aux + a
    return h, {"scan": new_scan, "tail": tuple(new_tail)}, aux


def _embed_tokens(cfg, params, tokens):
    x = params["embed"]["tokens"][tokens]
    return constrain(x, "batch", "seq", None)


def _build_inputs(cfg, params, batch):
    """Token/frontend embedding for train/prefill. Returns (h, n_prefix)."""
    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(_dtype(cfg))
        text = _embed_tokens(cfg, params, batch["tokens"])
        return jnp.concatenate([patches, text], axis=1), patches.shape[1]
    return _embed_tokens(cfg, params, batch["tokens"]), 0


def _logits(cfg, params, h):
    if cfg.tied_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"]["tokens"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"])
    return constrain(logits, "batch", "seq", "vocab")


def _encode(cfg, params, batch, mode="train"):
    """Encoder stack over precomputed source-frame embeddings (stub frontend)."""
    src = batch["src_frames"].astype(_dtype(cfg))
    s = src.shape[1]
    rope = rotary_embedding(jnp.arange(s), cfg.resolved_head_dim, cfg.rope_theta)
    enc_mode = "train" if mode != "decode" else "train"
    h, _, _ = _apply_stack(
        cfg, params, src, kinds_pattern=("attn",), scan_key="enc_scan",
        tail_key="_enc_tail_none", rope=rope, mode=enc_mode, causal=False,
    )
    return rms_norm(h, params["enc_final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Train / prefill / decode entry points
# ---------------------------------------------------------------------------

def forward(cfg: ArchConfig, params, batch) -> tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits, aux_loss)."""
    pattern = decoder_pattern(cfg)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = _encode(cfg, params, batch)
    h, n_prefix = _build_inputs(cfg, params, batch)
    s = h.shape[1]
    rope = rotary_embedding(jnp.arange(s), cfg.resolved_head_dim, cfg.rope_theta)
    h, _, aux = _apply_stack(
        cfg, params, h, kinds_pattern=pattern, scan_key="scan", tail_key="tail",
        rope=rope, mode="train", enc_out=enc_out,
    )
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _logits(cfg, params, h)
    if n_prefix:
        st = batch["tokens"].shape[1]
        logits = logits[:, n_prefix - 1 : n_prefix - 1 + st]
    return logits, aux


def _ce_terms(cfg, logits, targets, z_coef):
    """(Σ ce, Σ z, Σ valid) over one logits block; f32, padded vocab masked."""
    lf = logits.astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab_size:
        vmask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        lf = jnp.where(vmask[None, None, :], lf, -1e30)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    valid = (targets >= 0).astype(jnp.float32)
    return (
        jnp.sum((lse - gold) * valid),
        z_coef * jnp.sum(jnp.square(lse) * valid),
        jnp.sum(valid),
    )


def loss_fn(cfg: ArchConfig, params, batch, aux_coef: float = 0.01, z_coef: float = 1e-4):
    logits, aux = forward(cfg, params, batch)
    ce_sum, z_sum, n_valid = _ce_terms(cfg, logits, batch["targets"], z_coef)
    n_valid = jnp.maximum(n_valid, 1.0)
    ce = ce_sum / n_valid
    z_loss = z_sum / n_valid
    loss = ce + z_loss + aux_coef * aux
    metrics = {"loss": loss, "ce": ce, "aux": aux, "z_loss": z_loss, "tokens": n_valid}
    return loss, metrics


def prefill(cfg: ArchConfig, params, batch, cache):
    """Fill the decode cache from a full prompt; returns (cache, last_logits)."""
    pattern = decoder_pattern(cfg)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = _encode(cfg, params, batch, mode="prefill")
    h, n_prefix = _build_inputs(cfg, params, batch)
    s = h.shape[1]
    rope = rotary_embedding(jnp.arange(s), cfg.resolved_head_dim, cfg.rope_theta)
    h, new_caches, _ = _apply_stack(
        cfg, params, h, kinds_pattern=pattern, scan_key="scan", tail_key="tail",
        rope=rope, mode="prefill", caches=cache, enc_out=enc_out,
    )
    h = rms_norm(h[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = _logits(cfg, params, h)
    new_cache = {
        "pos": jnp.full((h.shape[0],), s, jnp.int32),
        "scan": new_caches["scan"],
        "tail": new_caches["tail"],
    }
    return new_cache, logits[:, 0]


def decode_step(cfg: ArchConfig, params, cache, tokens):
    """One decode step. tokens: (B, 1). Returns (cache, logits (B, vocab))."""
    pattern = decoder_pattern(cfg)
    pos = cache["pos"]
    h = _embed_tokens(cfg, params, tokens)
    h, new_caches, _ = _apply_stack(
        cfg, params, h, kinds_pattern=pattern, scan_key="scan", tail_key="tail",
        rope=None, mode="decode", caches=cache, pos=pos,
    )
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _logits(cfg, params, h)
    new_cache = {"pos": pos + 1, "scan": new_caches["scan"], "tail": new_caches["tail"]}
    return new_cache, logits[:, 0]
