"""jit'd wrapper for the flash attention kernel, with a custom VJP.

Public entry ``flash_attention(q, k, v, causal=..., window=...)`` takes the
model layout (B, S, H, hd) / (B, T, KV, hd), transposes to kernel layout,
runs the Pallas forward, and differentiates through the dq/dkv Pallas kernels.
Block sizes are shrunk to a tiling the TPU accepts; a sequence length that has
none raises instead of silently running another attention implementation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as K
from ..common import default_interpret


__all__ = ["flash_attention"]

_LANES = 128


def _pick_block(n: int, block: int) -> int:
    """The tile for a length-``n`` sequence axis: the whole axis when it fits
    in ``block``, else the largest multiple of 128 lanes ≤ ``block`` dividing
    ``n`` (Mosaic needs the lse/delta tiles (8, 128)-aligned or whole)."""
    if n <= block:
        return n
    for cand in range(block - block % _LANES, 0, -_LANES):
        if n % cand == 0:
            return cand
    raise ValueError(
        f"flash attention cannot tile a sequence of length {n} with blocks of at "
        f"most {block}: pad it to a multiple of {_LANES}, or use attn_impl='chunked'"
    )


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash(q, k, v, causal, window, block_q, block_k, interpret):
    out, _ = K.flash_fwd(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    return out


def _flash_fwd_rule(q, k, v, causal, window, block_q, block_k, interpret):
    out, lse = K.flash_fwd(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    kv = k.shape[1]
    group = q.shape[1] // kv
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, :, None]
    dq = K.flash_bwd_dq(
        q, k, v, do, lse, delta, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    dk_h, dv_h = K.flash_bwd_dkv(
        q, k, v, do, lse, delta, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    # reduce per-q-head grads onto the KV heads (GQA)
    b, h, t, hd = dk_h.shape
    dk = jnp.sum(dk_h.reshape(b, kv, group, t, hd), axis=2).astype(k.dtype)
    dv = jnp.sum(dv_h.reshape(b, kv, group, t, hd), axis=2).astype(v.dtype)
    return dq.astype(q.dtype), dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Model layout in/out: q (B,S,H,hd), k/v (B,T,KV,hd) -> (B,S,H,hd)."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    interpret = default_interpret(interpret)
    block_q, block_k = _pick_block(s, block_q), _pick_block(t, block_k)
    qt = jnp.swapaxes(q, 1, 2)  # (B,H,S,hd)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, causal, window, block_q, block_k, interpret)
    return jnp.swapaxes(out, 1, 2)
