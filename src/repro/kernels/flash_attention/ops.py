"""jit'd wrapper for the flash attention kernel, with a custom VJP.

Public entry ``flash_attention(q, k, v, causal=..., window=...)`` takes the
model layout (B, S, H, hd) / (B, T, KV, hd), transposes to kernel layout,
runs the Pallas forward, and differentiates through one Pallas backward
kernel that returns dq, dk and dv.
Block sizes come from the shape (:func:`block_sizes`), as tiles the TPU
accepts; a sequence length that has none raises instead of silently running
another attention implementation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import kernel as K
from ..common import default_interpret


__all__ = ["RESIDUAL_NAMES", "block_sizes", "flash_attention"]

#: the forward's residuals besides q, k, v: the output and the row logsumexp
RESIDUAL_NAMES = ("flash_attention_out", "flash_attention_lse")

_LANES = 128
#: the largest tiles :func:`block_sizes` picks at head_dim 128
_MAX_BLOCK_Q = 1024
_MAX_BLOCK_K = 1024


def _pick_block(n: int, block: int) -> int:
    """The tile for a length-``n`` sequence axis: the whole axis when it is at
    most 128 long, else the largest multiple of 128 lanes ≤ ``block`` dividing
    ``n`` (Mosaic needs the lse/delta tiles (8, 128)-aligned or whole)."""
    if n <= _LANES:
        return n
    for cand in range(block - block % _LANES, 0, -_LANES):
        if n % cand == 0:
            return cand
    raise ValueError(
        f"flash attention cannot tile a sequence of length {n} with blocks of at "
        f"most {block}: pad it to a multiple of {_LANES}, or use attn_impl='chunked'"
    )


def block_sizes(s: int, t: int, head_dim: int) -> tuple[int, int]:
    """(block_q, block_k) for S queries and T keys of width ``head_dim``: the
    largest tiles up to ``_MAX_BLOCK_Q`` x ``_MAX_BLOCK_K`` (halved for each
    doubling of the head past 128 lanes, so the tiles' VMEM stays put) that
    divide the axes."""
    shrink = max(1, head_dim // _LANES)
    return (
        _pick_block(s, max(_LANES, _MAX_BLOCK_Q // shrink)),
        _pick_block(t, max(_LANES, _MAX_BLOCK_K // shrink)),
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
    # named, so a remat policy can keep them and the backward need not run
    # the forward kernel again (the output also feeds the next matmul's grad)
    out, lse = (checkpoint_name(x, n) for x, n in zip((out, lse), RESIDUAL_NAMES))
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, :, None]
    return K.flash_bwd(
        q, k, v, do, lse, delta, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Model layout in/out: q (B,S,H,hd), k/v (B,T,KV,hd) -> (B,S,H,hd).

    ``block_q``/``block_k`` cap the tiles; by default :func:`block_sizes`
    picks them from the shape."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    interpret = default_interpret(interpret)
    auto_q, auto_k = block_sizes(s, t, hd)
    block_q = auto_q if block_q is None else _pick_block(s, block_q)
    block_k = auto_k if block_k is None else _pick_block(t, block_k)
    qt = jnp.swapaxes(q, 1, 2)  # (B,H,S,hd)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, causal, window, block_q, block_k, interpret)
    return jnp.swapaxes(out, 1, 2)
