"""RG-LRU Pallas TPU kernel: diagonal linear recurrence with VMEM-resident state.

grid = (B, n_d_blocks, n_chunks); the channel dimension is blocked (parallel)
and chunks advance sequentially ("arbitrary") with the (1, d_block) state held
in VMEM scratch.  Each chunk's a/b tiles are staged into f32 VMEM scratch
once; the token loop is a fori_loop that reads and writes one (1, d_block) row
per step through those refs (Mosaic has no ``dynamic_slice`` of a loaded
value, and a one-row dynamic slice of a packed bf16 tile is not 8-aligned) —
elementwise vector work; the op is bandwidth-bound and streams a/b tiles from
HBM exactly once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import default_interpret, tpu_compiler_params

__all__ = ["rglru_pallas"]


def _rglru_kernel(a_ref, b_ref, h0_ref, y_ref, hout_ref, state_scr, a_scr, b_scr, y_scr,
                  *, chunk, n_chunks):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = h0_ref[0].astype(jnp.float32)  # (1, D)

    a_scr[...] = a_ref[0].astype(jnp.float32)  # (C, D)
    b_scr[...] = b_ref[0].astype(jnp.float32)

    def step(t, h):  # h: (1, D)
        row = pl.ds(t, 1)
        h = a_scr[row, :] * h + b_scr[row, :]
        y_scr[row, :] = h
        return h

    h = jax.lax.fori_loop(0, chunk, step, state_scr[...])
    state_scr[...] = h
    y_ref[0] = y_scr[...].astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _finish():
        hout_ref[0] = h


def rglru_pallas(
    a: jax.Array,
    b: jax.Array,
    h0: jax.Array | None = None,
    chunk: int = 128,
    d_block: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """a, b: (B, S, D); h0: (B, D). Returns (h (B,S,D), final (B,D))."""
    bsz, s, d = a.shape
    interpret = default_interpret(interpret)
    if s % chunk != 0:
        chunk = s
    if d % d_block != 0:
        d_block = d
    n_chunks = s // chunk
    if h0 is None:
        h0 = jnp.zeros((bsz, d), jnp.float32)
    h0 = h0.astype(jnp.float32).reshape(bsz, 1, d)

    kernel = functools.partial(_rglru_kernel, chunk=chunk, n_chunks=n_chunks)
    y, hout = pl.pallas_call(
        kernel,
        grid=(bsz, d // d_block, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, chunk, d_block), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, 1, d_block), lambda bi, di, ci: (bi, 0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, 1, d_block), lambda bi, di, ci: (bi, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, d), a.dtype),
            jax.ShapeDtypeStruct((bsz, 1, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, d_block), jnp.float32)]
        + [pltpu.VMEM((chunk, d_block), jnp.float32)] * 3,
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary"), interpret
        ),
        interpret=interpret,
    )(a, b, h0)
    return y, hout[:, 0]
