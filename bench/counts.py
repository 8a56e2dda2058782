"""Operations and bytes that the benchmarked work needs, computed from shapes.

These are the numerators of every utilization the benchmark reports.  They
count what the algorithm needs, not what the program happens to do: causal
attention counts the query/key pairs at or below the diagonal, training
counts no recomputation, and a decode step reads the KV of each active row's
real positions, never the whole cache capacity.  A program that does less
work than counted here reads above 100% and is refused, so a count may be
low but never high.

Shapes are those of the repository's dense GQA decoder block (q, k, v, o
projections, SwiGLU MLP, two RMSNorms per layer, untied embedding and head).
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Dims", "train_step_flops", "prefill_flops", "decode_step_cost"]

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
