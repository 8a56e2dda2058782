"""The dense family's counts and the peak table against hand counts at a tiny
size, and its numbers for the benchmark's configurations as they stood before
the counts moved into ``bench/families``."""

import pytest

from bench.families.dense_gqa import Dims, decode_step_cost, prefill_flops, train_step_flops
from bench.peaks import peak_for

# d 64, 4 heads of 16, 2 K/V heads, MLP 128, vocab 256, 2 layers
SMALL = Dims(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab=256, n_layers=2)


def test_layer_and_head_params_by_hand():
    # q 64x64, k and v 64x32 each, o 64x64, gate/up/down 3 x 64x128
    assert SMALL.layer_matmul_params == 4096 + 2 * 2048 + 4096 + 3 * 8192 == 36864
    assert SMALL.head_params == 64 * 256


def test_train_flops_by_hand():
    # 6 x (2 layers x 36864 + 16384 head) x 64 tokens = 34,603,008
    dense = 6 * (2 * 36864 + 16384) * 64
    # causal pairs of a 32-token row: 32 * 33 / 2 = 528; QK^T and PV are
    # 2 FLOPs per pair per head dim each, x 4 heads x 16 dims x 2 layers,
    # x 3 for forward and backward, x 2 rows
    attention = 3 * 2 * (2 * 4 * 528 * 4 * 16)
    assert train_step_flops(SMALL, seq=32, n_seqs=2) == dense + attention == 36_225_024


def test_prefill_flops_by_hand():
    # 2 x 2 layers x 36864 x 10 tokens, 55 causal pairs, head on one token
    assert prefill_flops(SMALL, 10) == 2 * 2 * 36864 * 10 + 2 * 4 * 55 * 64 + 2 * 16384


def test_decode_cost_by_hand():
    flops, nbytes = decode_step_cost(SMALL, [10, 20])
    assert flops == 2 * 2 * (2 * 36864 + 16384) + 2 * 4 * 30 * 64
    # weights: 2 layers x (36864 matmul + 128 norm) + final norm 64 + head
    # 16384 + 2 embedding rows of 64, in bf16
    weights = (2 * (36864 + 128) + 64 + 16384 + 2 * 64) * 2
    per_position = 2 * 2 * 2 * 16 * 2  # layers x (K, V) x kv heads x head dim x bf16
    assert per_position == SMALL.kv_bytes_per_position
    logits = 2 * 256 * 2
    assert nbytes == weights + 30 * per_position + 2 * per_position + logits == 190_336


@pytest.mark.parametrize("max_seq", [64, 2568, 32768])
def test_decode_bytes_do_not_grow_with_the_cache(max_seq):
    # rows at positions 10 and 20 need the same bytes whatever the capacity
    # of the cache they sit in: the count takes only the positions attended
    rows = [min(10, max_seq), min(20, max_seq)]
    assert decode_step_cost(SMALL, rows) == decode_step_cost(SMALL, [10, 20])
    assert decode_step_cost(SMALL, []) == (0.0, 0.0)


def test_peaks_known_and_unknown():
    assert peak_for("TPU v5 lite").bf16_flops == 197e12
    assert peak_for("TPU v5 lite").hbm_bytes == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peak_for("TPU v9 imaginary")


def _family(config: str):
    from bench.harness import draft_cell, family_module

    cell = draft_cell("train.glm4-9b.4k", config, "pretrain-4k")
    return cell.config, family_module(cell)


@pytest.mark.parametrize("stages, batch, flops", [(0, 1, 12756254195712), (4, 8, 362432962756608)])
def test_train_flops_of_the_train_configuration(stages, batch, flops):
    config, F = _family("glm4-9b-train")
    n_layers = F.layers_for(config, {"pipeline_stages": stages})
    assert n_layers == 2 * max(stages, 1)
    assert F.train_step_flops(F.dims(config, n_layers), 4096, batch) == flops


def test_serve_counts_of_the_serve_configuration():
    config, F = _family("glm4-9b-serve")
    dims = F.dims(config, F.layers_for(config, {}))
    assert F.prefill_flops(dims, 2048) == 17396194607104
    assert F.prefill_flops(dims, 256) == 2100448722944
    assert F.decode_step_cost(dims, [1000, 2000, 2568]) == (30022828032, 9514799104)
