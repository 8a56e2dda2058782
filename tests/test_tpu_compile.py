"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes that are
not (8, 128)-aligned, ``dynamic_slice`` of loaded values, programs larger
than the device's memory.  These tests compile the main-path Pallas kernels
at published widths and the chip-sized llama3.2-1b train step for one v5e,
and check that every kernel lowered through Mosaic (``tpu_custom_call``).

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library at a time, and every
test worker imports every test file.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import block_sizes, flash_attention
from repro.kernels.rglru.kernel import rglru_pallas
from repro.kernels.rmsnorm.ops import rms_norm_fused
from repro.kernels.rwkv6.kernel import wkv6_pallas

V5E_HBM_BYTES = 16 * 2**30
SEQ = 2048
BATCH = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever the TPU library raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache but
    # cannot be read back without the chip; keep this file's compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _assert_mosaic(fn, *args):
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _sds(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _grad_of_sum(fn, argnums):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)), argnums=argnums)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "recurrentgemma-9b"])
def test_flash_attention_compiles_fwd_and_bwd(arch, one_chip):
    cfg = get_config(arch)
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    fn = functools.partial(flash_attention, causal=True, window=cfg.window, interpret=False)
    q = _sds(one_chip, (BATCH, SEQ, h, hd))
    k = _sds(one_chip, (BATCH, SEQ, kv, hd))
    _assert_mosaic(fn, q, k, k)
    _assert_mosaic(_grad_of_sum(fn, (0, 1, 2)), q, k, k)


def test_flash_attention_compiles_at_the_train_cell(one_chip):
    """GLM-4-9B's attention as the train cell runs it on one v5e: 1 x 4096,
    32 query heads over 2 K/V heads of 128, with the blocks the shape picks."""
    assert block_sizes(4096, 4096, 128) == (1024, 1024)
    fn = functools.partial(flash_attention, causal=True, interpret=False)
    q = _sds(one_chip, (1, 4096, 32, 128))
    k = _sds(one_chip, (1, 4096, 2, 128))
    _assert_mosaic(fn, q, k, k)
    _assert_mosaic(_grad_of_sum(fn, (0, 1, 2)), q, k, k)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "recurrentgemma-9b", "rwkv6-1.6b"])
def test_fused_rmsnorm_compiles_fwd_and_bwd(arch, one_chip):
    d = get_config(arch).d_model
    x = _sds(one_chip, (BATCH, SEQ, d))
    w = _sds(one_chip, (d,))
    fn = functools.partial(rms_norm_fused, interpret=False)
    _assert_mosaic(fn, x, w)
    _assert_mosaic(_grad_of_sum(fn, (0, 1)), x, w)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_compiles_at_recurrentgemma_width(dtype, one_chip):
    d = get_config("recurrentgemma-9b").d_model
    a = _sds(one_chip, (BATCH, SEQ, d), dtype)
    h0 = _sds(one_chip, (BATCH, d), jnp.float32)
    _assert_mosaic(functools.partial(rglru_pallas, interpret=False), a, a, h0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_compiles_at_rwkv6_width(dtype, one_chip):
    cfg = get_config("rwkv6-1.6b")
    h, hd = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    x = _sds(one_chip, (BATCH, SEQ, h, hd), dtype)
    u = _sds(one_chip, (h, hd), jnp.float32)
    state = _sds(one_chip, (BATCH, h, hd, hd), jnp.float32)
    _assert_mosaic(functools.partial(wkv6_pallas, interpret=False), x, x, x, x, u, state)


def test_llama_4_layer_train_step_fits_one_v5e(topo):
    """The chip-smoke training cell: published widths, 4 layers, seq 2048,
    global batch 2, AdamW state — arguments plus temporaries within HBM, and
    the default attention lowered to the flash kernel as on the chip."""
    from repro.launch.steps import make_train_step, rules_for
    from repro.models.config import ShapeConfig

    cfg = get_config("llama3.2-1b").replace(n_layers=4)
    mesh = Mesh(
        np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
    built = make_train_step(
        cfg, mesh, rules_for(cfg), ShapeConfig("train_chip", "train", SEQ, BATCH)
    )
    compiled = built.fn.lower(
        built.abstract_state["params"], built.abstract_state["opt_state"],
        *built.abstract_inputs,
    ).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, (mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape,want", [((1, 1), "pallas"), ((2, 2), "chunked")])
def test_default_attention_follows_the_described_chips(topo, shape, want):
    """From this CPU process a mesh of described v5e chips selects as the chip
    does: the kernel over one chip's arrays, the chunked scan over four."""
    from repro.dist.context import use_sharding
    from repro.launch.steps import rules_for
    from repro.models.attention import select_impl

    cfg = get_config("llama3.2-1b")
    n = shape[0] * shape[1]
    mesh = Mesh(np.asarray(topo.devices[:n]).reshape(shape), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    with use_sharding(mesh, rules_for(cfg)):
        assert select_impl("auto", SEQ, SEQ) == want
