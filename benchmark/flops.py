"""The benchmark's own count of the operations a trained token needs.

Every matrix multiplication of the layers and of the output head, once for
the forward pass and twice for the backward pass (2 FLOPs a multiply-add),
plus causal attention. Left out on purpose: the embedding table (a lookup,
no multiply), anything recomputed (remat, the pipeline's extra forward),
norms, rotary and softmax arithmetic.

The trainer's own `mfu` line (`utils/metrics.train_flops_per_token`) counts
`6 * N + 12 * L * d * S` with the embedding table inside N and non-causal
attention: `trainer_count` reproduces it so PERF.md can state the distance.
"""

from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Weights that take part in a matrix multiplication, per token."""
    d, f, v, n = (model["hidden_size"], model["intermediate_size"],
                  model["vocab_size"], model["num_hidden_layers"])
    kv_dim = model["num_key_value_heads"] * (d // model["num_attention_heads"])
    return n * (2 * d * d + 2 * d * kv_dim + 3 * d * f) + d * v


def train_flops_per_token(model: dict, seq_length: int) -> int:
    """6 FLOPs a matmul weight, and causal attention: QK^T and PV are
    2 * 2 * S * d a token forward when every position sees all S, half that
    under the causal mask, times three for forward and backward =
    6 * L * d * S."""
    n, d = model["num_hidden_layers"], model["hidden_size"]
    return 6 * matmul_params(model) + 6 * n * d * seq_length


def trainer_count(model: dict, seq_length: int) -> int:
    """What the program's own `mfu` divides by (for the record only)."""
    n, d, v = (model["num_hidden_layers"], model["hidden_size"],
               model["vocab_size"])
    total = matmul_params(model) + v * d + 2 * n * d + d   # + table + norms
    return 6 * total + 12 * n * d * seq_length
