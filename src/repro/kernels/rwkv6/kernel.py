"""RWKV-6 WKV Pallas TPU kernel.

TPU adaptation: GPU RWKV kernels keep the per-head (K,V) state
in registers/shared memory of one CTA and scan tokens sequentially.  On TPU we
keep the state in **VMEM scratch** that persists across the sequential chunk
dimension of the grid: grid = (B·H, n_chunks) with semantics
("parallel", "arbitrary"); each step streams a (chunk, K) tile of r/k/w and a
(chunk, V) tile of v from HBM, stages the tiles into f32 VMEM scratch, and
runs the token recurrence with VMEM-resident state, reading and writing one
token row per step through the scratch refs (Mosaic has no ``dynamic_slice``
of a loaded value, and a one-row slice of a packed bf16 tile is not
8-aligned).  The recurrence itself is vector-unit work (elementwise + small
outer products); the op is HBM-bandwidth-bound, which is exactly why streaming
chunks with a resident state is the right TPU shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import default_interpret, tpu_compiler_params

__all__ = ["wkv6_pallas"]


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                state_scr, r_scr, k_scr, v_scr, w_scr, y_scr, *, chunk, n_chunks):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)

    for ref, scr in ((r_ref, r_scr), (k_ref, k_scr), (v_ref, v_scr), (w_ref, w_scr)):
        scr[...] = ref[0].astype(jnp.float32)  # (C, K) / (C, V)
    u = u_ref[0].astype(jnp.float32)  # (1, K)

    def step(t, S):  # S: (K, V)
        row = pl.ds(t, 1)
        r_t = r_scr[row, :]  # (1, K)
        k_t = k_scr[row, :]
        v_t = v_scr[row, :]  # (1, V)
        w_t = w_scr[row, :]
        kv = k_t.T * v_t  # (K, V) outer product
        y_scr[row, :] = jnp.dot(r_t, S + u.T * kv, preferred_element_type=jnp.float32)
        return w_t.T * S + kv

    S = jax.lax.fori_loop(0, chunk, step, state_scr[...])
    state_scr[...] = S
    y_ref[0] = y_scr[...].astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _finish():
        sout_ref[0] = state_scr[...]


def wkv6_pallas(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    state: jax.Array | None = None,
    chunk: int = 64,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Shapes as ops.wkv6: r/k/w (B,S,H,K); v (B,S,H,V); u (H,K); state (B,H,K,V)."""
    b, s, h, dk = r.shape
    dv = v.shape[-1]
    interpret = default_interpret(interpret)
    if s % chunk != 0:
        chunk = s  # single block
    n_chunks = s // chunk
    if state is None:
        state = jnp.zeros((b, h, dk, dv), jnp.float32)

    # fold (B,H) -> one grid axis; layout (BH, S, K)
    def fold(x, d):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)

    rf, kf, wf = fold(r, dk), fold(k, dk), fold(w, dk)
    vf = fold(v, dv)
    uf = jnp.broadcast_to(u[None], (b, h, dk)).reshape(b * h, 1, dk)
    s0 = state.reshape(b * h, dk, dv)

    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(b * h, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, dv), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, dk), lambda bh, ci: (bh, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, dk, dv), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dv), r.dtype),
            jax.ShapeDtypeStruct((b * h, dk, dv), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((chunk, dk), jnp.float32),
            pltpu.VMEM((chunk, dk), jnp.float32),
            pltpu.VMEM((chunk, dv), jnp.float32),
            pltpu.VMEM((chunk, dk), jnp.float32),
            pltpu.VMEM((chunk, dv), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(("parallel", "arbitrary"), interpret),
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0)
    y = jnp.swapaxes(y.reshape(b, h, s, dv), 1, 2)
    return y, s_out.reshape(b, h, dk, dv)
