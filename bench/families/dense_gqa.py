"""The dense GQA decoder block: its configuration keys, the program's and the
reference's configs, and the operations and bytes its work needs.

Configuration files use the published ``config.json`` keys of the model
(ChatGLM naming for GLM-4); this module is the one place that maps them.

The counts are the numerators of every utilization the benchmark reports.
They count what the algorithm needs, not what the program happens to do:
causal attention counts the query/key pairs at or below the diagonal,
training counts no recomputation, and a decode step reads the KV of each
active row's real positions, never the whole cache capacity.  A program that
does less work than counted here reads above 100% and is refused, so a count
may be low but never high.

Shapes are those of the repository's dense GQA decoder block (q, k, v, o
projections, SwiGLU MLP, two RMSNorms per layer, untied embedding and head).
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Dims", "layers_for", "dims", "ref_arch", "program_cfg", "program_tree",
           "reference_leaves", "train_step_flops", "prefill_flops", "decode_step_cost"]

BF16 = 2  # bytes


@dataclass(frozen=True)
class Dims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    n_layers: int

    @property
    def layer_matmul_params(self) -> int:
        d, q, kv = self.d_model, self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def kv_bytes_per_position(self) -> int:
        """K and V of one position in every layer, bf16."""
        return self.n_layers * 2 * self.n_kv_heads * self.head_dim * BF16

    def attention_flops(self, pairs: int) -> int:
        """Forward FLOPs of QK^T and PV over ``pairs`` (query, key) pairs, all layers."""
        return self.n_layers * 4 * pairs * self.n_heads * self.head_dim


# ---------------------------------------------------------------------------
# The configuration file's keys
# ---------------------------------------------------------------------------

def layers_for(config: dict, traffic: dict) -> int:
    """Layers a cell runs: the file's ``num_layers`` per pipeline stage."""
    return int(config["num_layers"]) * max(int(traffic.get("pipeline_stages", 0)), 1)


def _numbers(config: dict, n_layers: int) -> dict:
    return dict(
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["multi_query_group_num"]),
        head_dim=int(config["kv_channels"]),
        d_ff=int(config["ffn_hidden_size"]),
        vocab=int(config["padded_vocab_size"]),
        n_layers=n_layers,
    )


def dims(config: dict, n_layers: int) -> Dims:
    return Dims(**_numbers(config, n_layers))


def ref_arch(config: dict, n_layers: int):
    from bench.reference.dense_gqa import Arch

    return Arch(
        **_numbers(config, n_layers),
        norm_eps=float(config["layernorm_epsilon"]),
        rope_theta=10000.0 * float(config["rope_ratio"]),
    )


def program_cfg(config: dict, n_layers: int):
    """The program's ``ArchConfig``: its registered config of this model with
    the file's numbers applied."""
    from repro.configs import get_config

    n = _numbers(config, n_layers)
    return get_config(config["program_arch"]).replace(
        name=config["name"],
        n_layers=n_layers,
        d_model=n["d_model"],
        n_heads=n["n_heads"],
        n_kv_heads=n["n_kv_heads"],
        head_dim=n["head_dim"],
        d_ff=n["d_ff"],
        vocab_size=n["vocab"],
        norm_eps=float(config["layernorm_epsilon"]),
        rope_theta=10000.0 * float(config["rope_ratio"]),
    )


def program_tree(flat: dict) -> dict:
    """The reference's flat leaves in the program's parameter tree."""
    return {
        "embed": {"tokens": flat["embed.tokens"]},
        "final_norm": flat["final_norm"],
        "lm_head": flat["lm_head"],
        "scan": ({
            "ln": flat["ln"], "ln2": flat["ln2"],
            "mlp": {"wg": flat["mlp.wg"], "wi": flat["mlp.wi"], "wo": flat["mlp.wo"]},
            "wk": flat["wk"], "wo": flat["wo"], "wq": flat["wq"], "wv": flat["wv"],
        },),
        "tail": (),
    }


def reference_leaves(tree) -> dict:
    """The program's parameter tree, or a tree of its shape (gradients,
    moments, master weights), as the reference's flat leaves: the inverse of
    :func:`program_tree`.  The stack is one kind of block and has no tail."""
    import jax

    (block,) = tree["scan"]
    if tree["tail"]:
        raise ValueError("the dense GQA stack has no tail")
    flat = {
        "embed.tokens": tree["embed"]["tokens"],
        "final_norm": tree["final_norm"],
        "lm_head": tree["lm_head"],
        "ln": block["ln"], "ln2": block["ln2"],
        "mlp.wg": block["mlp"]["wg"], "mlp.wi": block["mlp"]["wi"], "mlp.wo": block["mlp"]["wo"],
        "wk": block["wk"], "wo": block["wo"], "wq": block["wq"], "wv": block["wv"],
    }
    if len(jax.tree.leaves(tree)) != len(flat):
        raise ValueError("the program's tree holds leaves the reference does not name")
    return flat


# ---------------------------------------------------------------------------
# Operations and bytes
# ---------------------------------------------------------------------------

def _causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def train_step_flops(dims: Dims, seq: int, n_seqs: int) -> float:
    """Model FLOPs of one training step: 6 x (layer + head matmul params) x
    tokens, plus forward and backward (3x) of causal attention."""
    tokens = seq * n_seqs
    dense = 6 * (dims.n_layers * dims.layer_matmul_params + dims.head_params) * tokens
    return float(dense + 3 * n_seqs * dims.attention_flops(_causal_pairs(seq)))


def prefill_flops(dims: Dims, prompt_len: int) -> float:
    """Forward FLOPs of one prompt's prefill: every layer over every prompt
    token, causal attention, and the head on the last token only (that is all
    the prefill returns)."""
    dense = 2 * dims.n_layers * dims.layer_matmul_params * prompt_len
    return float(dense + dims.attention_flops(_causal_pairs(prompt_len)) + 2 * dims.head_params)


def decode_step_cost(dims: Dims, attended: list[int]) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one decode step over the active rows.

    ``attended[i]`` is how many cache positions row ``i`` attends to in this
    step, its new token included.  Bytes are the weights read once (every
    layer's matmuls and norms, the final norm, the head and one embedding row
    per active row), the KV of the attended positions, the K/V written for the
    new token and the bf16 logits written out.
    """
    rows = len(attended)
    if rows == 0:
        return 0.0, 0.0
    per_row = 2 * (dims.n_layers * dims.layer_matmul_params + dims.head_params)
    flops = rows * per_row + dims.attention_flops(sum(attended))
    weights = (
        dims.n_layers * (dims.layer_matmul_params + 2 * dims.d_model)
        + dims.d_model + dims.head_params + rows * dims.d_model
    ) * BF16
    kv_read = sum(attended) * dims.kv_bytes_per_position
    kv_write = rows * dims.kv_bytes_per_position
    logits = rows * dims.vocab * BF16
    return float(flops), float(weights + kv_read + kv_write + logits)
