"""Flash attention TPU kernels (Pallas): a forward and one backward kernel.

TPU adaptation: the online-softmax accumulators (m, l, acc)
live in VMEM scratch that persists across the *sequential* last grid dimension
(``arbitrary`` semantics) — the TPU analogue of FlashAttention's SRAM-resident
per-CTA accumulators.  Block shapes are (block_q|k, head_dim) with
head_dim ≥ 128-multiples feeding the MXU; masks are built from
``broadcasted_iota`` (TPU requires ≥2D iota).

Precision: every matmul takes its operands in the inputs' dtype (bf16 in
training) and accumulates in f32, as ``models.attention.chunked_attention``
does; the softmax statistics (``m``, ``l``, ``lse``, ``delta``) and the
scores stay f32, and ``p``/``ds`` are cast to the operands' dtype only to
enter the next matmul.

Layout: q (B, H, S, hd); k/v (B, KV, T, hd) — the ops wrapper transposes from
the model's (B, S, H, hd).  The per-row softmax statistics ``lse`` and
``delta`` are kept as (B, H, 1, S) so that their (1, block_q) tiles lie along
the lane axis: Mosaic requires the last two block dims to be (8, 128)-aligned
or whole, and a (block_q,) trailing block is neither.  GQA is handled by
indexing the KV head as ``h // group`` in the BlockSpec index maps.

The backward forms each tile's scores, probabilities and dP once and feeds
dV, dK and dQ from them (5 matmuls and one exp a tile).  Its grid walks a
K/V head's query heads and their q blocks, with the key blocks innermost:
dq sums over the key blocks in a (block_q, hd) scratch, and dk/dv sum over
the group's query heads and q blocks in (T, hd) scratch holding the K/V
head's whole sequence, written out once after its last tile.  Causal and
sliding-window masks are supported: tiles outside the mask's support are
skipped with ``pl.when`` and fetch nothing (their index maps repeat the
nearest needed block), and only tiles that straddle its edge are masked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import tpu_compiler_params

__all__ = ["flash_fwd", "flash_bwd"]

_NEG_INF = -2.0e38
#: Mosaic's scoped VMEM limit by default on a v5e
_VMEM_DEFAULT = 16 * 2**20
#: contract the last dims of both operands: a @ b.T without a transpose
_NT = (((1,), (1,)), ((), ()))
#: contract the first dims of both operands: a.T @ b
_TN = (((0,), (0,)), ((), ()))


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    return jax.lax.dot_general(a, b, _TN, preferred_element_type=jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _visible(shape, q_start, k_start, causal: bool, window: int | None, *, q_axis: int):
    """Which (query, key) pairs of a tile the mask keeps; queries run along
    ``q_axis`` of ``shape``."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    ok = jnp.ones(shape, jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window is not None:
        ok &= k_pos > q_pos - window
    return ok


def _block_needed(iq, ik, block_q, block_k, causal, window):
    """Whether tile (iq, ik) intersects the mask support (traced predicate)."""
    need = jnp.bool_(True)
    if causal:
        need &= ik * block_k <= iq * block_q + block_q - 1
    if window is not None:
        need &= (ik + 1) * block_k - 1 > iq * block_q - window
    return need


def _block_full(iq, ik, block_q, block_k, causal, window):
    """Whether the mask keeps every pair of tile (iq, ik)."""
    full = jnp.bool_(True)
    if causal:
        full &= ik * block_k + block_k - 1 <= iq * block_q
    if window is not None:
        full &= ik * block_k > iq * block_q + block_q - 1 - window
    return full


def _for_tile(compute, iq, ik, block_q, block_k, causal, window):
    """Run ``compute(masked)`` on a needed tile: unmasked where the mask
    keeps the whole tile, masked where the tile straddles its edge."""
    if not causal and window is None:
        compute(False)
        return
    full = _block_full(iq, ik, block_q, block_k, causal, window)
    pl.when(full)(lambda: compute(False))
    needed = _block_needed(iq, ik, block_q, block_k, causal, window)
    pl.when(needed & jnp.logical_not(full))(lambda: compute(True))


def _k_range(iq, block_q, block_k, n_k, causal, window):
    """First and last key block that query block ``iq`` needs."""
    lo, hi = 0, n_k - 1
    if window is not None:
        lo = jnp.maximum(0, (iq * block_q - window + 1) // block_k)
    if causal:
        hi = jnp.minimum(n_k - 1, (iq * block_q + block_q - 1) // block_k)
    return lo, hi


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _kv_index_map(group, block_q, block_k, n_k, causal, window):
    """K/V block of a (batch, head, q block, k block) grid step: the key block
    clamped to the range the query block needs, so a skipped step repeats
    the previous fetch."""
    def index(bi, hi, qi, ki):
        return (bi, hi // group, _clamp(ki, *_k_range(qi, block_q, block_k, n_k, causal, window)), 0)
    return index


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

    def compute(masked):
        v = v_ref[0, 0]
        s = _dot_nt(q_ref[0, 0], k_ref[0, 0]) * scale  # (bq, bk)
        if masked:
            ok = _visible(s.shape, iq * block_q, ik * block_k, causal, window, q_axis=0)
            s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _dot(p.astype(v.dtype), v)
        m_scr[...] = m_new

    _for_tile(compute, iq, ik, block_q, block_k, causal, window)

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

    kv_map = _kv_index_map(group, block_q, block_k, n_k, causal, window)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
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
# Backward: one kernel for dq, dk and dv (grid over a K/V head's query heads
# and their q blocks, scan the key blocks each q block needs).  Scores are
# formed transposed, (block_k, block_q), so the (1, block_q) lse/delta tiles
# broadcast along sublanes as they are loaded.
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                dq_scr, dk_scr, dv_scr,
                *, scale, block_q, block_k, group, n_q, n_k, causal, window):
    ig, iq, ik = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    kv_rows = pl.ds(pl.multiple_of(ik * block_k, block_k), block_k)

    @pl.when((ig == 0) & (iq == 0) & (ik == 0))
    def _init_kv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(ik == 0)
    def _init_q():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def compute(masked):
        q, k, do = q_ref[0, 0], k_ref[0, 0], do_ref[0, 0]
        s_t = _dot_nt(k, q) * scale  # (bk, bq)
        if masked:
            ok = _visible(s_t.shape, iq * block_q, ik * block_k, causal, window, q_axis=1)
            s_t = jnp.where(ok, s_t, _NEG_INF)
        p_t = jnp.exp(s_t - lse_ref[0, 0])
        dp_t = _dot_nt(v_ref[0, 0], do)
        ds_t = (p_t * (dp_t - delta_ref[0, 0])).astype(q.dtype)
        dv_scr[kv_rows] += _dot(p_t.astype(do.dtype), do)
        dk_scr[kv_rows] += _dot(ds_t, q)
        dq_scr[...] += _dot_tn(ds_t, k)

    _for_tile(compute, iq, ik, block_q, block_k, causal, window)

    @pl.when(ik == n_k - 1)
    def _finish_q():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)

    @pl.when((ig == group - 1) & (iq == n_q - 1) & (ik == n_k - 1))
    def _finish_kv():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_bwd(q, k, v, do, lse, delta, *, causal, window, block_q, block_k, interpret):
    """Returns (dq, dk, dv): dq of q's shape (B, H, S, hd), dk and dv of k's
    shape (B, KV, T, hd), summed over each K/V head's query heads."""
    b, h, s, hd = q.shape
    kv, t = k.shape[1], k.shape[2]
    group = h // kv
    n_q, n_k = s // block_q, t // block_k
    kernel = functools.partial(
        _bwd_kernel, scale=hd ** -0.5, block_q=block_q, block_k=block_k,
        group=group, n_q=n_q, n_k=n_k, causal=causal, window=window,
    )
    kv_block = _kv_index_map(group, block_q, block_k, n_k, causal, window)

    def q_map(bi, kvi, gi, qi, ki):
        return (bi, kvi * group + gi, qi, 0)

    def row_map(bi, kvi, gi, qi, ki):
        return (bi, kvi * group + gi, 0, qi)

    def kv_map(bi, kvi, gi, qi, ki):
        return kv_block(bi, kvi * group + gi, qi, ki)

    def kv_whole(bi, kvi, gi, qi, ki):
        return (bi, kvi, 0, 0)

    return pl.pallas_call(
        kernel,
        grid=(b, kv, group, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), q_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
            pl.BlockSpec((1, 1, block_q, hd), q_map),
            pl.BlockSpec((1, 1, 1, block_q), row_map),
            pl.BlockSpec((1, 1, 1, block_q), row_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd), q_map),
            pl.BlockSpec((1, 1, t, hd), kv_whole),
            pl.BlockSpec((1, 1, t, hd), kv_whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((t, hd), jnp.float32),
            pltpu.VMEM((t, hd), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary", "arbitrary", "arbitrary"), interpret,
            # the (T, hd) dk/dv accumulators and their two-buffered output blocks
            vmem_limit_bytes=_VMEM_DEFAULT + 2 * t * hd * (4 + 2 * k.dtype.itemsize),
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
