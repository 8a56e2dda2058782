"""Flash attention TPU kernels (Pallas): forward + backward (dq, dkv).

TPU adaptation (DESIGN.md §6): the online-softmax accumulators (m, l, acc)
live in VMEM scratch that persists across the *sequential* last grid dimension
(``arbitrary`` semantics) — the TPU analogue of FlashAttention's SRAM-resident
per-CTA accumulators.  Block shapes are (block_q|k, head_dim) with
head_dim ≥ 128-multiples feeding the MXU; masks are built from
``broadcasted_iota`` (TPU requires ≥2D iota).

Layout: q (B, H, S, hd); k/v (B, KV, T, hd) — the ops wrapper transposes from
the model's (B, S, H, hd).  The per-row softmax statistics ``lse`` and
``delta`` are kept as (B, H, 1, S) so that their (1, block_q) tiles lie along
the lane axis: Mosaic requires the last two block dims to be (8, 128)-aligned
or whole, and a (block_q,) trailing block is neither.  GQA is handled by
indexing the KV head as ``h // group`` in the BlockSpec index maps.  Causal and sliding-window masks
are supported; fully-masked K blocks are skipped with ``pl.when``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import tpu_compiler_params

__all__ = ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]

_NEG_INF = -2.0e38


def _mask(bias_shape, q_start, k_start, causal: bool, window: int | None):
    """Additive mask for a (block_q, block_k) tile, from absolute offsets."""
    bq, bk = bias_shape
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window is not None:
        ok &= k_pos > q_pos - window
    return jnp.where(ok, 0.0, _NEG_INF)


def _block_needed(iq, ik, block_q, block_k, causal, window):
    """Whether tile (iq, ik) intersects the mask support (traced predicate)."""
    need = jnp.bool_(True)
    if causal:
        need &= ik * block_k <= iq * block_q + block_q - 1
    if window is not None:
        need &= (ik + 1) * block_k - 1 > iq * block_q - window
    return need


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, block_q, block_k, n_k, causal, window):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_block_needed(iq, ik, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s += _mask((block_q, block_k), iq * block_q, ik * block_k, causal, window)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l)).T  # (bq, 1) -> (1, bq)


def flash_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
    interpret: bool,
):
    """q: (B,H,S,hd); k/v: (B,KV,T,hd). Returns (out (B,H,S,hd), lse (B,H,1,S))."""
    b, h, s, hd = q.shape
    kv, t = k.shape[1], k.shape[2]
    group = h // kv
    scale = hd ** -0.5
    n_q, n_k = s // block_q, t // block_k
    grid = (b, h, n_q, n_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_k=n_k, causal=causal, window=window,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary"), interpret
        ),
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward: dq (grid over q blocks, scan k blocks)
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
               *, scale, block_q, block_k, n_k, causal, window):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_block_needed(iq, ik, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        kb = k_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].T  # (1, bq) -> (bq, 1)
        delta = delta_ref[0, 0].T
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
        s += _mask((block_q, block_k), iq * block_q, ik * block_k, causal, window)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] += jnp.dot(ds, kb, preferred_element_type=jnp.float32) * scale

    @pl.when(ik == n_k - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def flash_bwd_dq(q, k, v, do, lse, delta, *, causal, window, block_q, block_k, interpret):
    b, h, s, hd = q.shape
    kv, t = k.shape[1], k.shape[2]
    group = h // kv
    n_q, n_k = s // block_q, t // block_k
    kernel = functools.partial(
        _dq_kernel, scale=hd ** -0.5, block_q=block_q, block_k=block_k,
        n_k=n_k, causal=causal, window=window,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
            pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary"), interpret
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# Backward: dk/dv (grid over k blocks, scan q blocks; per q-head, summed to
# KV heads by the ops wrapper)
# ---------------------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, block_q, block_k, n_q, causal, window):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_block_needed(iq, ik, block_q, block_k, causal, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        kb = k_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].T  # (1, bq) -> (bq, 1)
        delta = delta_ref[0, 0].T
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
        s += _mask((block_q, block_k), iq * block_q, ik * block_k, causal, window)
        p = jnp.exp(s - lse)  # (bq, bk)
        dv_scr[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32) * scale

    @pl.when(iq == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_bwd_dkv(q, k, v, do, lse, delta, *, causal, window, block_q, block_k, interpret):
    """Returns per-q-head (dk, dv) of shape (B, H, T, hd)."""
    b, h, s, hd = q.shape
    kv, t = k.shape[1], k.shape[2]
    group = h // kv
    n_q, n_k = s // block_q, t // block_k
    kernel = functools.partial(
        _dkv_kernel, scale=hd ** -0.5, block_q=block_q, block_k=block_k,
        n_q=n_q, causal=causal, window=window,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, ki, qi: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, ki, qi: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, ki, qi: (bi, hi, 0, qi)),
            pl.BlockSpec((1, 1, 1, block_q), lambda bi, hi, ki, qi: (bi, hi, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, hd), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, hd), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary"), interpret
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
