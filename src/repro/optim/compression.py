"""Gradient compression for cross-pod reduction (distributed-optimization trick).

Two pieces:

* **Error-feedback int8 quantization** (`ef_quantize` / `dequantize`): per-leaf
  symmetric int8 with an f32 scale; the quantization residual is carried in an
  error-feedback buffer added back before the next quantization, which keeps
  SGD/Adam convergence (Karimireddy et al. 2019 semantics).

* **Compressed cross-pod all-reduce** (`cross_pod_mean_compressed`): meant to
  run *inside* ``shard_map`` over the ``pod`` axis — all-gather the int8
  payload + f32 scales across pods and reduce locally.  For 2 pods this moves
  ~1 byte/param over the pod links instead of ~4 (bf16 ring all-reduce moves
  2·2 bytes/param), a ~4× collective-bytes cut on the slowest (inter-pod)
  links.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


__all__ = ["ef_quantize", "dequantize", "ef_init", "cross_pod_mean_compressed"]


def _q_leaf(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dq_leaf(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ef_init(tree):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)


def ef_quantize(tree, ef_buffer):
    """Quantize (tree + ef) to int8; returns (q_tree, scale_tree, new_ef)."""
    corrected = jax.tree.map(lambda g, e: g.astype(jnp.float32) + e, tree, ef_buffer)
    q_and_s = jax.tree.map(_q_leaf, corrected)
    q = jax.tree.map(lambda t: t[0], q_and_s, is_leaf=lambda t: isinstance(t, tuple))
    s = jax.tree.map(lambda t: t[1], q_and_s, is_leaf=lambda t: isinstance(t, tuple))
    new_ef = jax.tree.map(
        lambda c, qq, ss: c - _dq_leaf(qq, ss), corrected, q, s
    )
    return q, s, new_ef


def dequantize(q_tree, scale_tree):
    return jax.tree.map(_dq_leaf, q_tree, scale_tree)


def cross_pod_mean_compressed(tree, ef_buffer, axis_name: str = "pod"):
    """EF-int8 mean over `axis_name` (call inside shard_map over the pod axis).

    Returns (mean_tree_f32, new_ef_buffer).
    """
    n = jax.lax.psum(1, axis_name)
    q, s, new_ef = ef_quantize(tree, ef_buffer)

    def reduce_leaf(qq, ss):
        qg = jax.lax.all_gather(qq, axis_name)          # (pods, ...) int8
        sg = jax.lax.all_gather(ss, axis_name)          # (pods,) f32
        dq = qg.astype(jnp.float32) * sg.reshape((-1,) + (1,) * qq.ndim)
        return jnp.sum(dq, axis=0) / n

    mean = jax.tree.map(reduce_leaf, q, s)
    return mean, new_ef
