"""The float32 reference against the program (``repro.models``) at a tiny size.

Weights, loss and gradients, prefill, and decoding through the cache; and the
comparison the benchmark makes must reject the fp8 control.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers.train import tokens_at
from bench.families import dense_gqa as F
from bench.reference import dense_gqa as R
from conftest import TINY, TINY_LIMITS

CONFIG = dict(TINY, num_layers=2, layernorm_epsilon=1e-5, rope_ratio=1, program_arch="glm4-9b",
              name="tiny")
ARCH = F.ref_arch(CONFIG, 2)
SEED = 1234


@pytest.fixture(scope="module")
def M():
    from repro.models import model as M

    return M


def program_cfg(dtype="float32"):
    return F.program_cfg(CONFIG, 2).replace(dtype=dtype, attn_chunk=8)


# a one-bfloat16-step difference between a jitted draw and the program's is
# rare; 1408835725 (the key seed of --seed 3000000002) showed one in mlp.wo
@pytest.mark.parametrize("seed", [SEED, 1408835725, 7, 2**30 + 3])
def test_stacked_rule_is_the_programs_init(M, seed):
    got = R.stacked_weights(ARCH, seed)
    want = M.init_params(program_cfg("bfloat16"), jax.random.PRNGKey(seed))
    flat = F.reference_leaves(want)
    assert sorted(flat) == sorted(got)
    for name, leaf in flat.items():
        np.testing.assert_array_equal(np.asarray(leaf, np.float32), np.asarray(got[name]),
                                      err_msg=name)


@pytest.mark.parametrize("seed", [SEED, 7])
def test_per_layer_rule_is_what_the_reference_rebuilds(seed):
    """The served weights (one jitted call) and the reference's layer-by-layer
    rebuild of them are the same numbers."""
    served = R.make_weights(ARCH, seed, jnp.bfloat16)
    top = R.top_weights(ARCH, seed)
    for name, x in top.items():
        np.testing.assert_array_equal(np.asarray(served[name], np.float32), np.asarray(x))
    for i in range(ARCH.n_layers):
        for name, x in R.layer_weights(ARCH, seed, i).items():
            np.testing.assert_array_equal(np.asarray(served[name][i], np.float32), np.asarray(x))


def test_program_tree_matches_the_programs_params(M):
    flat = R.make_weights(ARCH, SEED, jnp.bfloat16)
    tree = F.program_tree(flat)
    want = M.abstract_params(program_cfg("bfloat16"))
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_train_data_is_the_programs_synthetic_stream():
    from repro.data import SyntheticConfig, SyntheticLM

    src = SyntheticLM(SyntheticConfig(256, 32, 2, mode="random", seed=SEED))
    for step in range(3):
        tokens, targets = tokens_at(SEED, step, 2, 32, 256)
        batch = src.batch_at(step)
        np.testing.assert_array_equal(batch["tokens"], tokens)
        np.testing.assert_array_equal(batch["targets"], targets)


def test_loss_and_grads_match_the_program(M):
    w = R.stacked_weights(ARCH, SEED)
    params = F.program_tree(w)
    tokens, targets = tokens_at(SEED, 0, 2, 32, 256)
    cfg = program_cfg()
    with jax.default_matmul_precision("highest"):
        pl, pg = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, {"tokens": tokens, "targets": targets})[0])(params)
        rl, rg = jax.value_and_grad(lambda w: R.loss_fn(ARCH, w, tokens, targets))(w)
    np.testing.assert_allclose(float(pl), float(rl), rtol=1e-5)
    got = F.reference_leaves(pg)
    for name, g in rg.items():
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(g), rtol=1e-3,
                                   atol=1e-5 * float(jnp.max(jnp.abs(g))), err_msg=name)


def _ref_logits(w, ids):
    h = w["embed.tokens"][jnp.asarray(ids)]
    for i in range(ARCH.n_layers):
        h = R.layer_apply(ARCH, {n: w[n][i] for n in R._STACKED}, h)
    return R._logits(ARCH, w, h, "f32")


def test_prefill_and_decode_through_the_cache_match(M):
    cfg = program_cfg()
    w = R.make_weights(ARCH, SEED, jnp.float32)
    params = F.program_tree(w)
    ids = list(np.random.default_rng(0).integers(0, 256, 20))
    prompt, follow = ids[:14], ids[14:]
    with jax.default_matmul_precision("highest"):
        want = _ref_logits(w, ids)
        cache = M.init_cache(cfg, 1, 32)
        cache, logits = M.prefill(cfg, params, {"tokens": jnp.asarray([prompt])}, cache)
        got = [logits[0]]
        for tok in follow[:-1]:
            cache, logits = M.decode_step(cfg, params, cache, jnp.asarray([[tok]]))
            got.append(logits[0])
    np.testing.assert_allclose(np.stack(got), np.asarray(want[13:19]), rtol=1e-4, atol=1e-4)


def _served(M, cfg, params, prompt, n):
    cache = M.init_cache(cfg, 1, 48)
    cache, logits = M.prefill(cfg, params, {"tokens": jnp.asarray([prompt])}, cache)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n - 1):
        cache, logits = M.decode_step(cfg, params, cache, jnp.asarray([[out[-1]]]))
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_token_gap_rejects_the_fp8_control(M):
    """The served-token comparison at this size's limit: the bf16 program
    passes it, the reference in fp8 (the control) fails it."""
    params = F.program_tree(R.make_weights(ARCH, SEED, jnp.bfloat16))
    cfg = program_cfg("bfloat16")
    rng = np.random.default_rng(5)
    seqs = []
    for _ in range(4):
        prompt = [int(x) for x in rng.integers(0, 256, 16)]
        seqs.append((prompt, _served(M, cfg, params, prompt, 12)))
    limit = TINY_LIMITS["serve.glm4-9b.chat"]["token_gap"]
    program = R.served_token_gaps(ARCH, SEED, seqs, pad_to=48)["widest_gap"]
    control = R.served_token_gaps(ARCH, SEED, seqs, pad_to=48, precision="fp8")["widest_gap"]
    assert program <= limit < control, (program, limit, control)


def test_train_comparison_rejects_the_fp8_control():
    """Three steps, compared as the training cell compares them: the fp8
    reference (the control) fails one of this size's limits."""
    from bench.drivers.train import compare

    batches = [tokens_at(SEED, i, 2, 32, 256) for i in range(3)]
    opt = R.AdamW(peak_lr=1e-3, warmup_steps=100, total_steps=1000)
    f32 = R.train_reference(ARCH, SEED, batches, opt)
    fp8 = R.train_reference(ARCH, SEED, batches, opt, precision="fp8")
    limits = TINY_LIMITS["train.glm4-9b.4k"]
    checks = compare({**fp8, "fed_differ": 0}, f32)
    assert any(c["value"] > limits[c["name"]] for c in checks), checks
