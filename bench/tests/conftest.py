"""CPU tests of the benchmark at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

#: the configurations' widths cut to a size the CPU runs in seconds
TINY = {
    "hidden_size": 64, "ffn_hidden_size": 128, "kv_channels": 16,
    "num_attention_heads": 4, "multi_query_group_num": 2, "padded_vocab_size": 256,
}


#: the limits at that size, set as the cells' are, from CPU readings there
#: (largest of 12 sound seeds, smallest of the fp8 control on 3: loss 3.86e-4
#: and 2.20e-3, grad 2.26e-3 and 1.08e-2, change 8.48e-4 and 3.12e-3, token
#: 1.75e-2 and 0.138; the four-chip cell's 8 layers on four virtual devices,
#: seeds 3000000000-11 of ``four_devices.py readings``: loss 2.42e-4 and
#: 8.31e-4, grad 2.31e-3 and 9.52e-3, change 7.88e-4 and 2.60e-3); the gaps
#: shrink as the widths grow, so the cells' own limits are tighter
TINY_LIMITS = {
    "train.glm4-9b.4k": {"loss_gap": 1e-3, "grad_gap": 5e-3, "change_gap": 1.6e-3,
                         "fed_tokens_differ": 0.0},
    "train.glm4-9b.pp4": {"loss_gap": 5e-4, "grad_gap": 5e-3, "change_gap": 1.6e-3,
                          "fed_tokens_differ": 0.0},
    "serve.glm4-9b.chat": {"token_gap": 0.05},
}


#: the per-layer metrics the chat cell would report, each read by its file
#: under ``bench/metrics``
SERVE_READERS = {
    "queue_p95_ms.serve": "ms", "engine_host_share.serve": "%",
    "prefill_us_per_token.serve": "us", "prefill_mfu.serve": "%",
    "decode_step_ms.serve": "ms", "decode_mfu.serve": "%", "decode_roofline.serve": "%",
    "device_idle_share.serve": "%",
}


def serve_cell():
    """The chat mix on the serving configuration, from its files alone: it is
    not a cell of BENCHMARK.json, since its tails cannot be held to a bound
    while the chip's completions now and then come seconds late (PERF.md,
    Open questions), but its driver, readers, reference and check are tested
    here all the same."""
    from bench.harness import draft_cell

    cell = draft_cell("serve.glm4-9b.chat", "glm4-9b-serve", "chat")
    cell.end_to_end = [{"name": "ttft_p95_ms", "unit": "ms"},
                       {"name": "tpot_p95_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}]
    cell.per_layer = [{"name": n, "unit": u} for n, u in SERVE_READERS.items()]
    return cell


#: the per-layer metrics the four-chip cell would report beside the train
#: cell's own
PP4_READERS = ("stage_busy_spread.train", "handoff_share.train")


def pp4_cell():
    """The four-chip 1F1B cell from its files alone: it is not a cell of
    BENCHMARK.json, since the program cannot start it on four v5e chips
    (PERF.md, Open questions), but its driver path, split reference, faults
    and readers are tested here all the same."""
    from bench.harness import draft_cell

    cell = draft_cell("train.glm4-9b.pp4", "glm4-9b-train", "pretrain-4k-pp4", chips=4)
    cell.end_to_end = [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}]
    cell.per_layer = [{"name": n, "unit": "%"} for n in PP4_READERS]
    return cell


def tiny_cell(name: str, **traffic):
    """A cell at tiny widths and lengths: one of BENCHMARK.json, the chat cell
    or the four-chip cell."""
    from bench.harness import load_cell

    drafts = {"serve.glm4-9b.chat": serve_cell, "train.glm4-9b.pp4": pp4_cell}
    cell = drafts[name]() if name in drafts else load_cell(name)
    cell.config = dict(cell.config, **TINY, num_layers=2)
    cell.traffic = dict(cell.traffic, **traffic)
    cell.limits = TINY_LIMITS[name]
    return cell


@pytest.fixture
def tiny_train():
    return tiny_cell("train.glm4-9b.4k", seq_len=32, global_batch=2, warmup_steps=4,
                     check_steps=3)


@pytest.fixture
def tiny_serve():
    return tiny_cell(
        "serve.glm4-9b.chat", rate_rps=40.0, lead_s=0.3, tail_arrivals_s=1.0, n_slots=4,
        max_seq=48, check_requests=3, drain_timeout_s=60,
        prompt={"distribution": "lognormal", "median": 12, "sigma": 0.5, "buckets": [8, 16]},
        output={"distribution": "lognormal", "median": 6, "sigma": 0.5, "min": 4, "max": 12},
    )


@pytest.fixture
def v5e():
    from bench.peaks import PEAKS

    return PEAKS["TPU v5 lite"]
