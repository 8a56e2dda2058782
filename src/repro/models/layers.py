"""Shared layer library: parameter specs, norms, rotary embeddings, MLPs.

Parameter handling convention
-----------------------------
Model code describes parameters with :class:`PSpec` trees (shape + logical axes
+ initializer).  ``materialize`` turns a spec tree into real arrays;
``abstract`` turns it into ``jax.ShapeDtypeStruct``s (shapes for building and
lowering a step without allocating); ``axes_tree`` extracts the logical
axes used by ``dist.sharding`` to build NamedShardings.
"""

from __future__ import annotations

from collections.abc import Sequence
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..dist.sharding import Axes


__all__ = [
    "PSpec",
    "materialize",
    "abstract",
    "axes_tree",
    "is_pspec",
    "rms_norm",
    "rotary_embedding",
    "apply_rotary",
    "gated_mlp_specs",
    "gated_mlp",
    "count_params",
]


@dataclass(frozen=True)
class PSpec:
    """Declarative parameter spec: shape, logical axes, init, dtype."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"  # normal | zeros | ones | const
    scale: float | None = None  # stddev for normal; default fan-in
    dtype: Any = jnp.bfloat16
    const: float = 0.0  # fill value for init == "const"

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"PSpec shape {self.shape} vs axes {self.axes}")


def is_pspec(x: Any) -> bool:
    return isinstance(x, PSpec)


def _fan_in(shape: Sequence[int]) -> int:
    # for stacked layer params the leading "layers" dim is not a fan-in dim;
    # use the second-to-last dim as fan-in (matmul convention: (..., in, out)).
    if len(shape) >= 2:
        return shape[-2]
    return max(shape[-1], 1)


def materialize(specs, key: jax.Array):
    """Instantiate a PSpec tree into concrete arrays."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_pspec)
    keys = jax.random.split(key, max(len(leaves), 1))
    arrays = []
    for spec, k in zip(leaves, keys):
        if spec.init == "zeros":
            arr = jnp.zeros(spec.shape, spec.dtype)
        elif spec.init == "ones":
            arr = jnp.ones(spec.shape, spec.dtype)
        elif spec.init == "const":
            arr = jnp.full(spec.shape, spec.const, spec.dtype)
        elif spec.init == "normal":
            scale = spec.scale if spec.scale is not None else _fan_in(spec.shape) ** -0.5
            arr = (jax.random.normal(k, spec.shape, jnp.float32) * scale).astype(spec.dtype)
        else:  # pragma: no cover
            raise ValueError(f"unknown init {spec.init!r}")
        arrays.append(arr)
    return jax.tree.unflatten(treedef, arrays)


def abstract(specs):
    """PSpec tree -> ShapeDtypeStruct tree (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs, is_leaf=is_pspec
    )


def axes_tree(specs):
    """PSpec tree -> logical-axes tree (same structure, Axes leaves)."""
    return jax.tree.map(lambda s: Axes(s.axes), specs, is_leaf=is_pspec)


def count_params(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_pspec)
    return int(sum(int(np.prod(s.shape)) for s in leaves))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm in f32, cast back to input dtype."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rotary_embedding(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for ``positions`` (any shape) -> (*pos.shape, head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Apply rotary embedding.

    x: (..., S, H, head_dim); cos/sin: (..., S, head_dim//2) broadcastable —
    typically (B, S, half) or (S, half).
    """
    dtype = x.dtype
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    # insert head axis for broadcast: cos (..., S, half) -> (..., S, 1, half)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU)
# ---------------------------------------------------------------------------

def gated_mlp_specs(d_model: int, d_ff: int, dtype, stack: tuple[int, ...] = ()) -> dict[str, PSpec]:
    lead = tuple(stack)
    lax = ("layers",) * len(stack)
    return {
        "wi": PSpec(lead + (d_model, d_ff), lax + ("embed", "ffn"), dtype=dtype),
        "wg": PSpec(lead + (d_model, d_ff), lax + ("embed", "ffn"), dtype=dtype),
        "wo": PSpec(lead + (d_ff, d_model), lax + ("ffn", "embed"), dtype=dtype),
    }


def gated_mlp(p: dict[str, jax.Array], x: jax.Array) -> jax.Array:
    up = jnp.einsum("bsd,df->bsf", x, p["wi"])
    gate = jnp.einsum("bsd,df->bsf", x, p["wg"])
    hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return jnp.einsum("bsf,fd->bsd", hidden, p["wo"])
