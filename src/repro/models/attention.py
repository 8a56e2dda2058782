"""Attention: GQA with causal / sliding-window masks, three implementations
and the default that chooses between two of them.

* ``naive``   — full (S×T) score matrix; the oracle (also ``kernels/flash_attention/ref.py``).
* ``chunked`` — exact attention, ``lax.scan`` over query chunks; each chunk
  computes an exact softmax against (a band of) K/V.  Under autodiff the scan
  keeps every chunk's (chunk, T) scores as residuals.  For sliding-window
  attention only the K/V band covering the window is sliced per chunk
  (compute O(S·window) instead of O(S²)).
* ``pallas``  — the flash-attention kernel (kernels/flash_attention): online
  softmax, causal blocks skipped, a Pallas backward; nothing of size S×T
  leaves the chip's fast memory.  Raises on shapes it cannot tile.
* ``auto``    — the default: ``pallas`` where :func:`select_impl` finds a TPU,
  S and T that tile by 128 and one device's arrays, ``chunked`` elsewhere (so
  every CPU run, tests included, takes ``chunked``).

Shapes: q (B, S, H, hd); k/v (B, T, KV, hd); GQA group = H // KV.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..dist.context import current_platform, current_sharding


__all__ = ["attention", "decode_attention", "select_impl"]

_NEG_INF = -2.0e38
#: the flash kernel's tiles are multiples of the TPU's 128 lanes
_FLASH_TILE = 128


def _split_heads(q: jax.Array, n_kv: int) -> jax.Array:
    """(B, S, H, hd) -> (B, S, KV, G, hd)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, hd)


def _merge_heads(x: jax.Array) -> jax.Array:
    b, s, kv, g, hd = x.shape
    return x.reshape(b, s, kv * g, hd)


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool, window: int | None) -> jax.Array:
    """(Sq, Tk) additive bias from position arrays."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32)


def naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """Full-matrix reference attention."""
    b, s, h, hd = q.shape
    t, n_kv = k.shape[1], k.shape[2]
    scale = hd ** -0.5
    qg = _split_heads(q, n_kv)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32) * scale
    q_pos = q_offset + jnp.arange(s)
    k_pos = jnp.arange(t)
    scores = scores + _mask_bias(q_pos, k_pos, causal, window)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return _merge_heads(out)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    chunk: int = 512,
    q_offset: int = 0,
) -> jax.Array:
    """Exact attention, scanned over query chunks (no S×T buffer)."""
    b, s, h, hd = q.shape
    t, n_kv = k.shape[1], k.shape[2]
    if s % chunk != 0 or s <= chunk:
        return naive_attention(q, k, v, causal=causal, window=window, q_offset=q_offset)
    n_chunks = s // chunk
    scale = hd ** -0.5
    qg = _split_heads(q, n_kv).reshape(b, n_chunks, chunk, n_kv, h // n_kv, hd)
    qg = jnp.moveaxis(qg, 1, 0)  # (nq, B, cq, KV, G, hd)

    # For sliding-window attention only a band of K/V is needed per q chunk.
    band = None
    if window is not None:
        band = min(t, ((window + chunk + 127) // 128) * 128)

    def body(_, inputs):
        qc, idx = inputs
        q_pos = q_offset + idx * chunk + jnp.arange(chunk)
        if band is not None and band < t:
            start = jnp.clip(idx * chunk - (band - chunk), 0, t - band)
            kc = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            k_pos = start + jnp.arange(band)
        else:
            kc, vc = k, v
            k_pos = jnp.arange(t)
        scores = jnp.einsum("bckgd,btkd->bkgct", qc, kc, preferred_element_type=jnp.float32) * scale
        ok = jnp.ones((chunk, k_pos.shape[0]), dtype=bool)
        if causal:
            ok &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            ok &= k_pos[None, :] > q_pos[:, None] - window
        scores = scores + jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgct,btkd->bckgd", probs.astype(vc.dtype), vc)
        return None, out

    _, outs = jax.lax.scan(body, None, (qg, jnp.arange(n_chunks)))
    outs = jnp.moveaxis(outs, 0, 1).reshape(b, s, n_kv, h // n_kv, hd)
    return _merge_heads(outs)


def select_impl(
    impl: str, s: int, t: int, *, q_offset: int = 0, devices: int | None = None
) -> str:
    """The implementation ``attention`` runs for ``impl`` over S queries and
    T keys: ``impl`` itself unless it is ``"auto"``.  ``"auto"`` runs the
    flash kernel when the platform (:func:`current_platform`) is a TPU, S and
    T tile by 128, the queries start at position 0, and the arrays are one
    device's: ``devices`` (by default the ambient sharding mesh's size, 1
    outside one, as in the pipeline's per-device stages) is 1.  A
    multi-device jit stays chunked: XLA would gather the kernel's inputs onto
    every device."""
    if impl != "auto":
        return impl
    if devices is None:
        context = current_sharding()
        devices = 1 if context is None else context[0].size
    flash = (
        current_platform() == "tpu"
        and s % _FLASH_TILE == 0
        and t % _FLASH_TILE == 0
        and q_offset == 0
        and devices == 1
    )
    return "pallas" if flash else "chunked"


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "auto",
    causal: bool = True,
    window: int | None = None,
    chunk: int = 512,
    q_offset: int = 0,
) -> jax.Array:
    impl = select_impl(impl, q.shape[1], k.shape[1], q_offset=q_offset)
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal, window=window, q_offset=q_offset)
    if impl == "chunked":
        return chunked_attention(
            q, k, v, causal=causal, window=window, chunk=chunk, q_offset=q_offset
        )
    if impl == "pallas":
        from ..kernels.flash_attention import ops as fa_ops

        return fa_ops.flash_attention(q, k, v, causal=causal, window=window)
    raise ValueError(f"unknown attention impl {impl!r}")


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    kv_valid: jax.Array,
) -> jax.Array:
    """Single-token attention against a cache.

    q: (B, 1, H, hd); k_cache/v_cache: (B, T, KV, hd); kv_valid: (B, T) bool.
    """
    b, _, h, hd = q.shape
    n_kv = k_cache.shape[2]
    scale = hd ** -0.5
    qg = _split_heads(q, n_kv)[:, 0]  # (B, KV, G, hd)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache, preferred_element_type=jnp.float32) * scale
    scores = jnp.where(kv_valid[:, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs.astype(v_cache.dtype), v_cache)
    return out.reshape(b, 1, h, hd)
