"""Per-kind layer blocks: attn / attn_local / xattn / rglru / rwkv.

Every block exposes three pieces:
  * ``block_specs(cfg, kind, stack)``        — PSpec tree for one layer,
  * ``block_cache_specs(cfg, kind, B, Smax)`` — PSpec tree for its decode cache,
  * ``block_apply(cfg, kind, p, h, ...)``     — forward in one of three modes:
      "train"   : full sequence, no cache,
      "prefill" : full sequence, returns a filled cache,
      "decode"  : one token against the cache (S == 1).

Cache design: global attention keeps (B, Smax, KV, hd) K/V written at
absolute positions; sliding-window attention keeps a **ring buffer** of
``window`` slots plus per-slot absolute positions (this is what keeps the
hybrid arch's long-context decode sub-quadratic); RG-LRU keeps the (B, D) f32
recurrence state and the (B, cw-1, D) conv tail; RWKV keeps the (B, H, K, V)
f32 WKV state and the two token-shift vectors.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..dist.context import constrain
from ..kernels.rglru import ops as rglru_ops
from ..kernels.rmsnorm.ops import rms_norm_fused
from ..kernels.rwkv6 import ops as rwkv_ops
from .attention import attention, decode_attention
from .config import ArchConfig
from .layers import PSpec, apply_rotary, gated_mlp, gated_mlp_specs, rms_norm, rotary_embedding
from .moe import moe_apply, moe_specs

__all__ = ["block_specs", "block_cache_specs", "block_apply", "norm"]

_RWKV_LORA = 64


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def norm(cfg: ArchConfig, x: jax.Array, weight: jax.Array) -> jax.Array:
    """Config-dispatched rmsnorm: the unfused reference or the Pallas fused
    kernel (``cfg.norm_impl == "fused"``; interpret-mode off-TPU).  Both sides
    compute in f32 and return ``x.dtype`` — identical dtype contract."""
    if cfg.norm_impl == "fused":
        return rms_norm_fused(x, weight, cfg.norm_eps)
    return rms_norm(x, weight, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def _attn_specs(cfg: ArchConfig, stack: tuple[int, ...], prefix_cross: bool = False) -> dict[str, Any]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    dt = _dtype(cfg)
    lead, lax_ = tuple(stack), ("layers",) * len(stack)
    specs: dict[str, Any] = {
        "ln": PSpec(lead + (d,), lax_ + (None,), init="ones", dtype=dt),
        "wq": PSpec(lead + (d, cfg.q_dim), lax_ + ("embed", "heads"), dtype=dt),
        "wk": PSpec(lead + (d, cfg.kv_dim), lax_ + ("embed", "kv_heads"), dtype=dt),
        "wv": PSpec(lead + (d, cfg.kv_dim), lax_ + ("embed", "kv_heads"), dtype=dt),
        "wo": PSpec(lead + (cfg.q_dim, d), lax_ + ("heads", "embed"), dtype=dt),
    }
    if cfg.use_qk_norm:
        specs["qn"] = PSpec(lead + (hd,), lax_ + (None,), init="ones", dtype=dt)
        specs["kn"] = PSpec(lead + (hd,), lax_ + (None,), init="ones", dtype=dt)
    return specs


def _ffn_specs(cfg: ArchConfig, stack: tuple[int, ...]) -> dict[str, Any]:
    lead, lax_ = tuple(stack), ("layers",) * len(stack)
    dt = _dtype(cfg)
    out: dict[str, Any] = {
        "ln2": PSpec(lead + (cfg.d_model,), lax_ + (None,), init="ones", dtype=dt)
    }
    if cfg.moe is not None:
        out["moe"] = moe_specs(cfg, stack)
    else:
        out["mlp"] = gated_mlp_specs(cfg.d_model, cfg.d_ff, dt, stack)
    return out


def _rglru_specs(cfg: ArchConfig, stack: tuple[int, ...]) -> dict[str, Any]:
    d = cfg.d_model
    dt = _dtype(cfg)
    lead, lax_ = tuple(stack), ("layers",) * len(stack)
    return {
        "ln": PSpec(lead + (d,), lax_ + (None,), init="ones", dtype=dt),
        "w_in": PSpec(lead + (d, d), lax_ + ("embed", "ffn"), dtype=dt),
        "w_gate": PSpec(lead + (d, d), lax_ + ("embed", "ffn"), dtype=dt),
        "conv": PSpec(lead + (cfg.conv_width, d), lax_ + ("conv", "ffn"), scale=0.5, dtype=dt),
        "rg_a": PSpec(lead + (d, d), lax_ + ("ffn", None), dtype=dt),
        "b_a": PSpec(lead + (d,), lax_ + (None,), init="zeros", dtype=dt),
        "rg_x": PSpec(lead + (d, d), lax_ + ("ffn", None), dtype=dt),
        "b_x": PSpec(lead + (d,), lax_ + (None,), init="zeros", dtype=dt),
        "lam": PSpec(lead + (d,), lax_ + (None,), init="ones", dtype=jnp.float32),
        "w_out": PSpec(lead + (d, d), lax_ + ("ffn", "embed"), dtype=dt),
    }


def _rwkv_specs(cfg: ArchConfig, stack: tuple[int, ...]) -> dict[str, Any]:
    d, h, k = cfg.d_model, cfg.rwkv_n_heads, cfg.rwkv_head_dim
    dt = _dtype(cfg)
    lead, lax_ = tuple(stack), ("layers",) * len(stack)
    vec = lambda name=None, init="normal", scale=0.02: PSpec(  # noqa: E731
        lead + (d,), lax_ + (None,), init=init, scale=scale, dtype=dt
    )
    return {
        "ln1": vec(init="ones"),
        "mu_r": vec(),
        "mu_k": vec(),
        "mu_v": vec(),
        "mu_w": vec(),
        "mu_g": vec(),
        "w_r": PSpec(lead + (d, d), lax_ + ("embed", "heads"), dtype=dt),
        "w_k": PSpec(lead + (d, d), lax_ + ("embed", "heads"), dtype=dt),
        "w_v": PSpec(lead + (d, d), lax_ + ("embed", "heads"), dtype=dt),
        "w_g": PSpec(lead + (d, d), lax_ + ("embed", "heads"), dtype=dt),
        "w_o": PSpec(lead + (d, d), lax_ + ("heads", "embed"), dtype=dt),
        "w0": PSpec(lead + (d,), lax_ + (None,), init="zeros", dtype=jnp.float32),
        "w_lora_a": PSpec(lead + (d, _RWKV_LORA), lax_ + ("embed", None), dtype=dt),
        "w_lora_b": PSpec(lead + (_RWKV_LORA, d), lax_ + (None, "heads"), dtype=dt),
        "u": PSpec(lead + (h, k), lax_ + ("heads", None), scale=0.5, dtype=jnp.float32),
        "gn": vec(init="ones"),
        "ln2": vec(init="ones"),
        "mu_ck": vec(),
        "mu_cr": vec(),
        "w_ck": PSpec(lead + (d, cfg.d_ff), lax_ + ("embed", "ffn"), dtype=dt),
        "w_cv": PSpec(lead + (cfg.d_ff, d), lax_ + ("ffn", "embed"), dtype=dt),
        "w_cr": PSpec(lead + (d, d), lax_ + ("embed", None), dtype=dt),
    }


def block_specs(cfg: ArchConfig, kind: str, stack: tuple[int, ...] = ()) -> dict[str, Any]:
    if kind in ("attn", "attn_local"):
        specs = _attn_specs(cfg, stack)
        specs.update(_ffn_specs(cfg, stack))
        return specs
    if kind == "xattn":  # decoder block: self-attn + cross-attn + ffn
        specs = {"self": _attn_specs(cfg, stack)}
        specs["cross"] = _attn_specs(cfg, stack)
        specs.update(_ffn_specs(cfg, stack))
        return specs
    if kind == "rglru":
        specs = {"rnn": _rglru_specs(cfg, stack)}
        specs.update(_ffn_specs(cfg, stack))
        return specs
    if kind == "rwkv":
        return _rwkv_specs(cfg, stack)
    raise ValueError(f"unknown block kind {kind!r}")


# ---------------------------------------------------------------------------
# Cache specs
# ---------------------------------------------------------------------------

def block_cache_specs(
    cfg: ArchConfig, kind: str, batch: int, max_seq: int, stack: tuple[int, ...] = ()
) -> dict[str, Any]:
    d, hd, kv = cfg.d_model, cfg.resolved_head_dim, cfg.n_kv_heads
    dt = _dtype(cfg)
    lead, lax_ = tuple(stack), ("layers",) * len(stack)
    if kind == "attn":
        kvshape = lead + (batch, max_seq, kv, hd)
        kvaxes = lax_ + ("batch", "kv_seq", "kv_heads", "head_dim")
        return {
            "k": PSpec(kvshape, kvaxes, init="zeros", dtype=dt),
            "v": PSpec(kvshape, kvaxes, init="zeros", dtype=dt),
        }
    if kind == "attn_local":
        w = min(cfg.window or max_seq, max_seq)
        kvshape = lead + (batch, w, kv, hd)
        kvaxes = lax_ + ("batch", None, "kv_heads", "head_dim")
        return {
            "k": PSpec(kvshape, kvaxes, init="zeros", dtype=dt),
            "v": PSpec(kvshape, kvaxes, init="zeros", dtype=dt),
            "slot_pos": PSpec(
                lead + (batch, w), lax_ + ("batch", None), init="const", const=-1,
                dtype=jnp.int32,
            ),
        }
    if kind == "xattn":
        self_cache = block_cache_specs(cfg, "attn", batch, max_seq, stack)
        # cross K/V over the encoder memory; filled once at prefill
        enc_len = max_seq
        kvshape = lead + (batch, enc_len, kv, hd)
        kvaxes = lax_ + ("batch", "kv_seq", "kv_heads", "head_dim")
        return {
            "self": self_cache,
            "xk": PSpec(kvshape, kvaxes, init="zeros", dtype=dt),
            "xv": PSpec(kvshape, kvaxes, init="zeros", dtype=dt),
        }
    if kind == "rglru":
        return {
            "h": PSpec(lead + (batch, d), lax_ + ("batch", "ffn"), init="zeros", dtype=jnp.float32),
            "conv": PSpec(
                lead + (batch, cfg.conv_width - 1, d),
                lax_ + ("batch", None, "ffn"),
                init="zeros",
                dtype=dt,
            ),
        }
    if kind == "rwkv":
        h, k = cfg.rwkv_n_heads, cfg.rwkv_head_dim
        return {
            "wkv": PSpec(
                lead + (batch, h, k, k),
                lax_ + ("batch", "heads", None, None),
                init="zeros",
                dtype=jnp.float32,
            ),
            "shift_tm": PSpec(lead + (batch, d), lax_ + ("batch", None), init="zeros", dtype=dt),
            "shift_cm": PSpec(lead + (batch, d), lax_ + ("batch", None), init="zeros", dtype=dt),
        }
    raise ValueError(f"unknown block kind {kind!r}")


# ---------------------------------------------------------------------------
# Attention blocks
# ---------------------------------------------------------------------------

def _project_qkv(cfg, p, x):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"]).reshape(b, s, cfg.n_heads, hd)
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.use_qk_norm:
        q = norm(cfg, q, p["qn"])
        k = norm(cfg, k, p["kn"])
    return q, k, v


def _attn_core_train(cfg, p, h, rope, *, window, causal, mode, cache):
    """Self-attention over a full sequence (train or prefill)."""
    x = norm(cfg, h, p["ln"])
    q, k, v = _project_qkv(cfg, p, x)
    if rope is not None:
        cos, sin = rope
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    att = attention(
        q, k, v, impl=cfg.attn_impl, causal=causal, window=window, chunk=cfg.attn_chunk
    )
    att = constrain(att, "batch", "seq", "heads", None)
    out = jnp.einsum("bsq,qd->bsd", att.reshape(att.shape[0], att.shape[1], -1), p["wo"])
    h = h + out
    new_cache = None
    if mode == "prefill":
        s = k.shape[1]
        if window is None:
            if cache is not None and cache["k"].shape[1] != s:
                ck = cache["k"].at[:, :s].set(k)
                cv = cache["v"].at[:, :s].set(v)
            else:
                ck, cv = k, v
            new_cache = {"k": ck, "v": cv}
        else:
            w = min(window, s) if cache is None else cache["k"].shape[1]
            w_fill = min(s, w)
            positions = jnp.arange(s - w_fill, s)
            slots = positions % w
            ck = jnp.zeros((k.shape[0], w, k.shape[2], k.shape[3]), k.dtype)
            cv = jnp.zeros_like(ck)
            sp = jnp.full((k.shape[0], w), -1, jnp.int32)
            ck = ck.at[:, slots].set(k[:, s - w_fill:])
            cv = cv.at[:, slots].set(v[:, s - w_fill:])
            sp = sp.at[:, slots].set(positions.astype(jnp.int32))
            new_cache = {"k": ck, "v": cv, "slot_pos": sp}
    return h, new_cache


def _attn_core_decode(cfg, p, h, cache, pos, *, window):
    """One-token self-attention against the cache. h: (B,1,D); pos: (B,)."""
    x = norm(cfg, h, p["ln"])
    q, k, v = _project_qkv(cfg, p, x)
    cos, sin = rotary_embedding(pos[:, None], cfg.resolved_head_dim, cfg.rope_theta)
    q = apply_rotary(q, cos, sin)
    k = apply_rotary(k, cos, sin)
    b = q.shape[0]
    if window is None:
        ck = cache["k"].at[jnp.arange(b), pos].set(k[:, 0])
        cv = cache["v"].at[jnp.arange(b), pos].set(v[:, 0])
        valid = jnp.arange(ck.shape[1])[None, :] <= pos[:, None]
        new_cache = {"k": ck, "v": cv}
    else:
        w = cache["k"].shape[1]
        slot = pos % w
        ck = cache["k"].at[jnp.arange(b), slot].set(k[:, 0])
        cv = cache["v"].at[jnp.arange(b), slot].set(v[:, 0])
        sp = cache["slot_pos"].at[jnp.arange(b), slot].set(pos.astype(jnp.int32))
        valid = (sp >= 0) & (sp > (pos[:, None] - window)) & (sp <= pos[:, None])
        new_cache = {"k": ck, "v": cv, "slot_pos": sp}
    att = decode_attention(q, ck, cv, valid)
    out = jnp.einsum("bsq,qd->bsd", att.reshape(b, 1, -1), p["wo"])
    return h + out, new_cache


def _ffn_apply(cfg, p, h):
    x = norm(cfg, h, p["ln2"])
    if cfg.moe is not None:
        y, aux = moe_apply(cfg, p["moe"], x)
    else:
        y, aux = gated_mlp(p["mlp"], x), jnp.zeros((), jnp.float32)
    return h + y, aux


# ---------------------------------------------------------------------------
# RG-LRU block
# ---------------------------------------------------------------------------

def _causal_conv(p_conv, x, state):
    """Depthwise causal conv, width cw. x: (B,S,D); state: (B,cw-1,D) or None."""
    cw = p_conv.shape[0]
    b, s, d = x.shape
    if state is None:
        state = jnp.zeros((b, cw - 1, d), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # (B, S+cw-1, D)
    y = sum(xp[:, j : j + s] * p_conv[j][None, None, :] for j in range(cw))
    new_state = xp[:, s:]  # last cw-1 inputs
    return y, new_state


def _rglru_gates(cfg, p, u):
    """Compute decay a and driven input b for the recurrence (f32)."""
    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", u, p["rg_a"]).astype(jnp.float32) + p["b_a"])
    i = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", u, p["rg_x"]).astype(jnp.float32) + p["b_x"])
    log_a = -cfg.rglru_c * jax.nn.softplus(p["lam"]) * r
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * uf)
    return a, b


def _rglru_block(cfg, p, h, *, mode, cache):
    rp = p["rnn"]
    x = norm(cfg, h, rp["ln"])
    u = jnp.einsum("bsd,de->bse", x, rp["w_in"])
    gate = jax.nn.gelu(jnp.einsum("bsd,de->bse", x, rp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    conv_state = cache["conv"] if cache is not None else None
    u, new_conv = _causal_conv(rp["conv"], u, conv_state)
    a, bdrive = _rglru_gates(cfg, rp, u)
    if mode == "decode":
        h0 = cache["h"]
        hseq = a[:, 0] * h0 + bdrive[:, 0]
        new_h = hseq
        hseq = hseq[:, None].astype(x.dtype)
    else:
        h0 = cache["h"] if cache is not None else None
        hseq, new_h = rglru_ops.linear_recurrence(a, bdrive, h0)
        hseq = hseq.astype(x.dtype)
    y = jnp.einsum("bse,ed->bsd", hseq * gate, rp["w_out"])
    h = h + y
    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"h": new_h.astype(jnp.float32), "conv": new_conv}
    return h, new_cache


# ---------------------------------------------------------------------------
# RWKV-6 block
# ---------------------------------------------------------------------------

def _token_shift(x, state):
    """xprev_t = x_{t-1}; first position takes `state` (or zero)."""
    b, s, d = x.shape
    first = state[:, None] if state is not None else jnp.zeros((b, 1, d), x.dtype)
    if s == 1:
        return first
    return jnp.concatenate([first, x[:, :-1]], axis=1)


def _rwkv_block(cfg, p, h, *, mode, cache):
    b, s, d = h.shape
    nh, hd = cfg.rwkv_n_heads, cfg.rwkv_head_dim
    # --- time mix ---
    x = norm(cfg, h, p["ln1"])
    xprev = _token_shift(x, cache["shift_tm"] if cache is not None else None)
    mix = lambda mu: x + (xprev - x) * mu[None, None, :]  # noqa: E731
    r = jnp.einsum("bsd,de->bse", mix(p["mu_r"]), p["w_r"]).reshape(b, s, nh, hd)
    k = jnp.einsum("bsd,de->bse", mix(p["mu_k"]), p["w_k"]).reshape(b, s, nh, hd)
    v = jnp.einsum("bsd,de->bse", mix(p["mu_v"]), p["w_v"]).reshape(b, s, nh, hd)
    g = jnp.einsum("bsd,de->bse", mix(p["mu_g"]), p["w_g"])
    w_dyn = jnp.einsum(
        "bsr,rd->bsd", jnp.tanh(jnp.einsum("bsd,dr->bsr", mix(p["mu_w"]), p["w_lora_a"])),
        p["w_lora_b"],
    )
    logw = -jnp.exp(jnp.clip(p["w0"][None, None, :] + w_dyn.astype(jnp.float32), -8.0, 8.0))
    w = jnp.exp(logw).reshape(b, s, nh, hd)
    state = cache["wkv"] if cache is not None else None
    impl = "ref" if mode == "decode" else "chunked"
    y, new_state = rwkv_ops.wkv6(r, k, v, w, p["u"], state, impl=impl)
    # per-head group norm, gate, out projection
    y = norm(cfg, y, jnp.ones((hd,), y.dtype)).reshape(b, s, d)
    y = y * p["gn"][None, None, :].astype(y.dtype)
    y = y * jax.nn.silu(g.astype(jnp.float32)).astype(y.dtype)
    h = h + jnp.einsum("bse,ed->bsd", y, p["w_o"])
    new_shift_tm = x[:, -1]
    # --- channel mix ---
    x2 = norm(cfg, h, p["ln2"])
    x2prev = _token_shift(x2, cache["shift_cm"] if cache is not None else None)
    mix2 = lambda mu: x2 + (x2prev - x2) * mu[None, None, :]  # noqa: E731
    kc = jnp.einsum("bsd,df->bsf", mix2(p["mu_ck"]), p["w_ck"])
    kc = jnp.square(jax.nn.relu(kc.astype(jnp.float32))).astype(x2.dtype)
    rc = jax.nn.sigmoid(
        jnp.einsum("bsd,de->bse", mix2(p["mu_cr"]), p["w_cr"]).astype(jnp.float32)
    ).astype(x2.dtype)
    h = h + rc * jnp.einsum("bsf,fd->bsd", kc, p["w_cv"])
    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"wkv": new_state, "shift_tm": new_shift_tm, "shift_cm": x2[:, -1]}
    return h, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (enc-dec)
# ---------------------------------------------------------------------------

def _cross_attn(cfg, p, h, enc_out=None, cache=None, pos=None, mode="train"):
    """Cross-attention: queries from h, K/V from encoder memory."""
    b = h.shape[0]
    x = norm(cfg, h, p["ln"])
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"]).reshape(b, x.shape[1], cfg.n_heads, hd)
    if mode == "decode":
        xk, xv = cache["xk"], cache["xv"]
        valid = jnp.ones((b, xk.shape[1]), bool)
        att = decode_attention(q, xk, xv, valid)
        new_kv = None
    else:
        xk = jnp.einsum("bsd,dq->bsq", enc_out, p["wk"]).reshape(
            b, enc_out.shape[1], cfg.n_kv_heads, hd
        )
        xv = jnp.einsum("bsd,dq->bsq", enc_out, p["wv"]).reshape(
            b, enc_out.shape[1], cfg.n_kv_heads, hd
        )
        att = attention(q, xk, xv, impl=cfg.attn_impl, causal=False, chunk=cfg.attn_chunk)
        new_kv = (xk, xv)
    out = jnp.einsum("bsq,qd->bsd", att.reshape(b, att.shape[1], -1), p["wo"])
    return h + out, new_kv


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def block_apply(
    cfg: ArchConfig,
    kind: str,
    p: dict[str, Any],
    h: jax.Array,
    *,
    rope=None,
    mode: str = "train",
    cache: dict[str, Any] | None = None,
    pos: jax.Array | None = None,
    enc_out: jax.Array | None = None,
    causal: bool = True,
) -> tuple[jax.Array, dict[str, Any] | None, jax.Array]:
    """Apply one block. Returns (h, new_cache, aux_loss)."""
    zero = jnp.zeros((), jnp.float32)
    if kind in ("attn", "attn_local"):
        window = cfg.window if kind == "attn_local" else None
        if mode == "decode":
            h, new_attn = _attn_core_decode(cfg, p, h, cache, pos, window=window)
        else:
            h, new_attn = _attn_core_train(
                cfg, p, h, rope, window=window, causal=causal, mode=mode, cache=cache
            )
        h, aux = _ffn_apply(cfg, p, h)
        return h, new_attn, aux
    if kind == "xattn":
        if mode == "decode":
            h, new_self = _attn_core_decode(cfg, p["self"], h, cache["self"], pos, window=None)
            h, _ = _cross_attn(cfg, p["cross"], h, cache=cache, mode="decode")
            new_cache = {"self": new_self, "xk": cache["xk"], "xv": cache["xv"]}
        else:
            h, new_self = _attn_core_train(
                cfg, p["self"], h, rope, window=None, causal=True, mode=mode,
                cache=cache["self"] if cache is not None else None,
            )
            h, new_kv = _cross_attn(cfg, p["cross"], h, enc_out=enc_out, mode=mode)
            new_cache = None
            if mode == "prefill":
                new_cache = {"self": new_self, "xk": new_kv[0], "xv": new_kv[1]}
        h, aux = _ffn_apply(cfg, p, h)
        return h, new_cache, aux
    if kind == "rglru":
        h, new_rnn = _rglru_block(cfg, p, h, mode=mode, cache=cache)
        h, aux = _ffn_apply(cfg, p, h)
        return h, new_rnn, aux
    if kind == "rwkv":
        h, new_cache = _rwkv_block(cfg, p, h, mode=mode, cache=cache)
        return h, new_cache, zero
    raise ValueError(f"unknown block kind {kind!r}")
