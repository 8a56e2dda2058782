"""Flash-attention Pallas kernel vs pure-jnp oracle: shape/dtype sweeps +
gradient checks, all in interpret mode (CPU container; Mosaic on real TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


SWEEP = [
    # (B, S, T, H, KV, hd, dtype, causal, window)
    (1, 128, 128, 2, 2, 64, jnp.float32, True, None),
    (2, 256, 256, 4, 2, 64, jnp.float32, True, None),     # GQA
    (1, 128, 256, 2, 1, 64, jnp.float32, False, None),    # cross-shape, MQA
    (2, 256, 256, 4, 4, 32, jnp.float32, True, 128),      # sliding window
    (1, 128, 128, 2, 2, 128, jnp.bfloat16, True, None),   # bf16, MXU-width head
    (1, 256, 256, 8, 2, 64, jnp.bfloat16, True, 64),      # bf16 + window + GQA
]


@pytest.mark.parametrize("b,s,t,h,kv,hd,dtype,causal,window", SWEEP)
def test_flash_forward_matches_ref(b, s, t, h, kv, hd, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (b, s, h, hd), dtype)
    k = _rand(ks[1], (b, t, kv, hd), dtype)
    v = _rand(ks[2], (b, t, kv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("causal,window", [(True, None), (False, None), (True, 128)])
def test_flash_gradients_match_ref(causal, window):
    b, s, h, kv, hd = 1, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (b, s, h, hd), jnp.float32)
    k = _rand(ks[1], (b, s, kv, hd), jnp.float32)
    v = _rand(ks[2], (b, s, kv, hd), jnp.float32)

    def f_k(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, window=window, interpret=True) ** 2)

    def f_r(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=causal, window=window) ** 2)

    gk = jax.grad(f_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_r, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("s", [100, 384])
def test_flash_falls_back_on_untiled_shapes(s):
    """Shapes the default 128-blocks do not tile still run the kernel: a short
    axis as one whole tile (100), a longer one with the largest 128-multiple
    block that divides it (384 with block 256 -> 128)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (1, s, 2, 64), jnp.float32)
    k = _rand(ks[1], (1, s, 2, 64), jnp.float32)
    v = _rand(ks[2], (1, s, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=256, block_k=256, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_refuses_shapes_with_no_legal_tiling():
    q = jnp.zeros((1, 300, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="cannot tile"):
        flash_attention(q, q, q, causal=True, interpret=True)


def test_flash_jit_compatible():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (1, 128, 2, 64), jnp.float32)
    k = _rand(ks[1], (1, 128, 2, 64), jnp.float32)
    v = _rand(ks[2], (1, 128, 2, 64), jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=True))
    np.testing.assert_allclose(
        np.asarray(f(q, k, v)), np.asarray(attention_ref(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5,
    )
