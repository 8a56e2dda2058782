"""Which attention the default path runs, and the flash kernel at the train
cell's head grouping.

``attention(impl="auto")`` (the default) runs the flash kernel only on a TPU,
for S and T that tile by 128, over one device's arrays; everywhere else, and
so on this CPU backend, it runs ``chunked_attention``.  The platform is
steered inside each test; no program option selects it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, Mesh

from repro.configs import get_config, get_smoke_config
from repro.core.clocks import counter_values
from repro.dist.context import current_platform, use_sharding
from repro.kernels.common import default_interpret
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import attention_ref
from repro.launch.steps import rules_for
from repro.models import attention as A
from repro.models import model as M


def _rand(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)


# -- the selection -------------------------------------------------------------

@pytest.mark.parametrize(
    "s,t,backend,devices,want",
    [
        (4096, 4096, "tpu", 1, "pallas"),     # the train cell on one chip
        (512, 1024, "tpu", 1, "pallas"),      # cross-attention, T != S
        (4096, 4096, "cpu", 1, "chunked"),    # every CPU run
        (4000, 4096, "tpu", 1, "chunked"),    # S does not tile by 128
        (4096, 1000, "tpu", 1, "chunked"),    # T does not tile by 128
        (4096, 4096, "tpu", 4, "chunked"),    # a multi-device jit
    ],
)
def test_auto_selects_flash_only_for_tpu_tiles_on_one_device(s, t, backend, devices, want, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert A.select_impl("auto", s, t, devices=devices) == want


def test_explicit_impl_is_kept_and_query_offsets_stay_chunked(monkeypatch):
    for impl in ("naive", "chunked", "pallas"):
        assert A.select_impl(impl, 4000, 4000, devices=4) == impl
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert A.select_impl("auto", 4096, 4096, q_offset=128, devices=1) == "chunked"


@pytest.mark.parametrize("shape,want", [((2, 2), "chunked"), ((1, 1), "pallas")])
def test_ambient_mesh_decides_one_device(shape, want, monkeypatch):
    """Inside the train step's sharding context the mesh's size counts: a
    (data, model) = (2, 2) mesh stays chunked, a one-device mesh takes the
    kernel; outside any context (the pipeline's per-device stages) the
    arrays are one device's.  An abstract mesh names no platform, so the
    default backend's counts."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = AbstractMesh(shape, ("data", "model"))
    cfg = get_smoke_config("llama3.2-1b")
    with use_sharding(mesh, rules_for(cfg)):
        assert A.select_impl("auto", 4096, 4096) == want
    assert A.select_impl("auto", 4096, 4096) == "pallas"


def test_ambient_mesh_devices_decide_the_platform(monkeypatch):
    """A mesh of devices names the platform, whatever the default backend:
    one CPU device's mesh stays chunked, and its kernels interpret."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(jax.devices("cpu")[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    with use_sharding(mesh, rules_for(get_smoke_config("llama3.2-1b"))):
        assert current_platform() == "cpu"
        assert A.select_impl("auto", 4096, 4096) == "chunked"
        assert default_interpret(None)
    assert not default_interpret(None)


def test_default_attention_dispatch(monkeypatch):
    """The default ``attention`` call runs chunked here and the kernel where
    the backend reads as a TPU."""
    calls = []

    def spy(name, fn):
        def wrapped(*args, **kwargs):
            calls.append(name)
            return fn(*args, **kwargs)
        return wrapped

    monkeypatch.setattr(A, "chunked_attention", spy("chunked", A.chunked_attention))
    monkeypatch.setattr(fa_ops, "flash_attention", spy("flash", A.naive_attention))
    q = _rand(0, (1, 256, 4, 32), jnp.float32)
    kv = _rand(1, (1, 256, 2, 32), jnp.float32)
    assert jax.default_backend() == "cpu"
    A.attention(q, kv, kv)
    assert calls == ["chunked"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    A.attention(q, kv, kv)
    A.attention(q[:, :200], kv[:, :200], kv[:, :200])
    assert calls == ["chunked", "flash", "chunked"]


# -- the counters --------------------------------------------------------------

def _glm4_9b_train():
    """The train cell's program config: published GLM-4-9B widths, 2 layers,
    a 1/8 vocabulary slice."""
    return get_config("glm4-9b").replace(n_layers=2, vocab_size=18944)


@pytest.mark.parametrize("backend,want", [("tpu", (2, 0)), ("cpu", (0, 2))])
def test_attention_counters_at_the_train_cell(backend, want, monkeypatch):
    from repro.launch.train import attention_layers_by_path

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert attention_layers_by_path(_glm4_9b_train(), 4096, devices=1) == want
    # a (2, 2) mesh keeps the chunked scan on a TPU too
    assert attention_layers_by_path(_glm4_9b_train(), 4096, devices=4) == (0, 2)


def test_attention_layers_count_every_full_sequence_attention():
    assert M.attention_layers(_glm4_9b_train()) == 2
    rg = get_config("recurrentgemma-9b")
    assert M.attention_layers(rg) == sum(k == "attn_local" for k in rg.layer_kinds())
    sm = get_config("seamless-m4t-large-v2")
    assert M.attention_layers(sm) == 2 * sm.n_layers + sm.n_enc_layers
    assert M.attention_layers(get_config("rwkv6-1.6b")) == 0


def test_training_run_records_its_attention_path():
    """A CPU run counts its attention layers under ``attn_chunked_layers``,
    once, at startup."""
    from repro.launch.train import TrainSettings, run_training
    from repro.timing import TimingSession

    channels = ["attn_flash_layers", "attn_chunked_layers"]
    before = counter_values(channels)
    settings = TrainSettings(arch="llama3.2-1b", smoke=True, steps=2, global_batch=2,
                             seq_len=32, ckpt_mode="off", report_every=0)
    run_training(settings, session=TimingSession())
    flash, chunked = (a - b for a, b in zip(counter_values(channels), before))
    assert (flash, chunked) == (0, M.attention_layers(get_smoke_config("llama3.2-1b")))


def test_dots_remat_keeps_the_flash_forward():
    """Under the default ``remat="dots"`` the backward reads the kernel's
    saved output and logsumexp: the step runs as many kernels as without
    remat (the forward and the one backward), where ``"full"`` remat runs the
    forward again."""
    cfg = get_smoke_config("llama3.2-1b").replace(attn_impl="pallas")
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    batch = {"tokens": jnp.zeros((1, 128), jnp.int32),
             "targets": jnp.zeros((1, 128), jnp.int32)}

    def kernels(remat):
        c = cfg.replace(remat=remat)
        grad = jax.grad(lambda p: M.loss_fn(c, p, batch)[0])
        return str(jax.make_jaxpr(grad)(params)).count("pallas_call")

    assert kernels("dots") == kernels("none") == 2
    assert kernels("full") == 3


# -- the kernel at the train cell's head grouping ---------------------------------

#: bf16 operands with f32 accumulation against an f32 reference on the same
#: bf16 inputs: the largest error over the largest reference entry
BF16_OUT_TOL = 1e-2
BF16_GRAD_TOL = 2e-2


def _ref_f32(q, k, v):
    with jax.default_matmul_precision("highest"):
        return attention_ref(*(x.astype(jnp.float32) for x in (q, k, v)), causal=True)


def _rel_err(got, want):
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(np.asarray(got, np.float32) - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("blocks", [None, (128, 256), (256, 128)])
def test_flash_gqa16_bf16_matches_f32_reference(blocks):
    """32 query heads over 2 K/V heads (GLM-4-9B's group of 16), seq 512,
    head 128, bf16: the blocks the shape picks (one 512 tile) and smaller
    ones that skip, clamp and mask causal tiles.  The kernel's error stays
    within the stated bounds and within 1.5x of the chunked path's."""
    b, s, h, kv, hd = 1, 512, 32, 2, 128
    q, k, v = _rand(1, (b, s, h, hd)), _rand(2, (b, s, kv, hd)), _rand(3, (b, s, kv, hd))
    do = _rand(4, (b, s, h, hd))
    bq, bk = blocks if blocks else (None, None)
    assert fa_ops.block_sizes(s, s, hd) == (512, 512)

    def flash(q, k, v):
        return fa_ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                                      interpret=True)

    def chunked(q, k, v):
        return A.chunked_attention(q, k, v, causal=True, chunk=128)

    want_o, ref_vjp = jax.vjp(_ref_f32, q, k, v)
    want_g = ref_vjp(do.astype(jnp.float32))
    for name, fn in (("flash", flash), ("chunked", chunked)):
        out, vjp = jax.vjp(fn, q, k, v)
        assert out.dtype == jnp.bfloat16
        grads = vjp(do)
        errs = [_rel_err(out, want_o)] + [_rel_err(g, w) for g, w in zip(grads, want_g)]
        if name == "flash":
            flash_errs = errs
            assert errs[0] <= BF16_OUT_TOL, errs
            assert max(errs[1:]) <= BF16_GRAD_TOL, errs
        else:
            for got, base in zip(flash_errs, errs):
                assert got <= 1.5 * base + 1e-3, (flash_errs, errs)
