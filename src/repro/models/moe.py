"""Mixture-of-Experts FFN: top-k token-choice routing with static capacity.

Expert-parallel design: expert-stacked weights carry the
``expert`` logical axis (sharded over the ``model`` mesh axis), and dispatch is
gather/scatter-based — tokens are packed into an (E, C) slot buffer with
``take``/scatter-add, *not* with GShard's dense one-hot dispatch einsums, so
HLO FLOPs reflect useful compute only.  Tokens beyond an expert's capacity
``C = ceil(top_k·S·cf/E)`` are dropped (their residual passes through), the
standard static-shape discipline.

Router math in f32; Switch-style load-balancing aux loss.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..dist.context import constrain
from .config import ArchConfig
from .layers import PSpec

__all__ = ["moe_specs", "moe_apply"]


def moe_specs(cfg: ArchConfig, stack: tuple[int, ...] = ()) -> dict[str, PSpec]:
    assert cfg.moe is not None
    d, e, de = cfg.d_model, cfg.moe.n_experts, cfg.moe.d_expert
    lead = tuple(stack)
    lax_ = ("layers",) * len(stack)
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return {
        "router": PSpec(lead + (d, e), lax_ + ("embed", "expert"), dtype=jnp.float32),
        "wi": PSpec(lead + (e, d, de), lax_ + ("expert", "embed", "expert_ffn"), dtype=dtype),
        "wg": PSpec(lead + (e, d, de), lax_ + ("expert", "embed", "expert_ffn"), dtype=dtype),
        "wo": PSpec(lead + (e, de, d), lax_ + ("expert", "expert_ffn", "embed"), dtype=dtype),
    }


def moe_apply(
    cfg: ArchConfig, p: dict[str, jax.Array], x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss).

    Routing groups: for S > 1 each batch row is its own routing group (keeps
    dispatch local to the batch shard).  For decode (S == 1) the *batch* is the
    token group — with per-row grouping every row would run all E experts at
    capacity 1, inflating FLOPs E/k-fold.  Batch-grouping instead produces the
    cross-device token shuffle that expert parallelism implies (XLA inserts the
    all-to-all).
    """
    b, s, d = x.shape
    if s == 1 and b > 1:
        y, aux = _moe_grouped(cfg, p, x.reshape(1, b, d))
        return y.reshape(b, 1, d), aux
    return _moe_grouped(cfg, p, x)


def _moe_grouped(
    cfg: ArchConfig, p: dict[str, jax.Array], x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Dispatch/combine are written per-row and ``vmap``ed over the batch, so
    every scatter/gather carries the batch as an *operand batch dimension* —
    SPMD partitions those along the (sharded) batch axis instead of replicating
    the full global buffer (advanced-indexing scatters with explicit batch
    index arrays force SPMD's "involuntary full rematerialization" and
    all-reduces of replicated (B_global, S, D) buffers)."""
    moe = cfg.moe
    b, s, d = x.shape
    e, k = moe.n_experts, moe.top_k
    cap = max(math.ceil(k * s * moe.capacity_factor / e), 1)
    cap = min(cap, s)

    gates = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"]), axis=-1
    )  # (B,S,E) f32
    top_v, top_i = jax.lax.top_k(gates, k)  # (B,S,k)
    top_v = top_v / jnp.maximum(jnp.sum(top_v, axis=-1, keepdims=True), 1e-9)

    def route_row(xr: jax.Array, tv: jax.Array, ti: jax.Array):
        """xr (S,D), tv/ti (S,k) -> (xe (E,C,D), token_idx (E,C), w_slot (E,C),
        mask (S,E))."""
        combine = jnp.zeros((s, e), jnp.float32)
        combine = combine.at[jnp.arange(s)[:, None], ti].add(tv)
        mask = (combine > 0).astype(jnp.int32)
        pos = jnp.cumsum(mask, axis=0) - 1
        keep = (mask == 1) & (pos < cap)
        slot = jnp.where(keep, pos, cap)  # (S,E); overflow slot sliced off
        token_idx = jnp.full((e, cap + 1), s, jnp.int32)
        token_idx = token_idx.at[
            jnp.broadcast_to(jnp.arange(e)[None, :], (s, e)), slot
        ].set(jnp.broadcast_to(jnp.arange(s)[:, None], (s, e)))
        token_idx = token_idx[:, :cap]  # (E,C); sentinel = s
        xp = jnp.concatenate([xr, jnp.zeros((1, d), xr.dtype)], axis=0)
        xe = xp[token_idx]  # (E,C,D)
        w_slot = combine[token_idx, jnp.arange(e)[:, None]]
        w_slot = jnp.where(token_idx < s, w_slot, 0.0)
        return xe, token_idx, w_slot, mask

    xe, token_idx, w_slot, expert_mask = jax.vmap(route_row)(x, top_v, top_i)
    xe = constrain(xe, "batch", "expert", None, "embed")

    up = jnp.einsum("becd,edf->becf", xe, p["wi"])
    gate = jnp.einsum("becd,edf->becf", xe, p["wg"])
    hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(xe.dtype) * up
    ye = jnp.einsum("becf,efd->becd", hidden, p["wo"])
    ye = constrain(ye, "batch", "expert", None, "embed")

    def combine_row(ye_r: jax.Array, ti_r: jax.Array, ws_r: jax.Array):
        # accumulate in the activation dtype: each token receives ≤ top_k adds
        # (distinct slots), and the EP combine all-reduce over the model axis
        # moves half the bytes vs f32
        y_pad = jnp.zeros((s + 1, d), x.dtype)
        y_pad = y_pad.at[ti_r].add((ye_r.astype(jnp.float32) * ws_r[..., None]).astype(x.dtype))
        return y_pad[:s]

    y = jax.vmap(combine_row)(ye, token_idx, w_slot)
    y = constrain(y, "batch", "seq", None)

    # Switch load-balancing loss: E * Σ_e f_e · p̄_e
    frac = jnp.mean(expert_mask.astype(jnp.float32), axis=(0, 1))  # (E,)
    prob = jnp.mean(gates, axis=(0, 1))
    aux = e * jnp.sum(frac * prob)
    return y, aux
