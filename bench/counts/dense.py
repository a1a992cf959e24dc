"""Operations of the dense GQA decoder, from its configuration file.

Model operations only: what the algorithm needs, not what an
implementation computes (no recomputation, no masked-out attention, no
idle batch rows).  A multiply-add counts two.
"""

from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Weights every token multiplies: the layers' projections and the
    output head (the embedding lookup multiplies nothing)."""
    d, f, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    hq = c["num_attention_heads"] * c["head_dim"]
    hkv = c["num_key_value_heads"] * c["head_dim"]
    per_layer = d * (hq + 2 * hkv) + hq * d + 3 * d * f
    return L * per_layer + d * c["vocab_size"]


def attention_flops(c: dict, context: float) -> float:
    """Forward score and value products of one query token against
    ``context`` keys, over all layers."""
    return (4.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * context)


def decode_flops(c: dict, positions) -> float:
    """One decode token per entry of ``positions`` (the token's index in
    its sequence; it attends over ``position + 1`` keys)."""
    n = len(positions)
    return (2.0 * matmul_params(c) * n
            + sum(attention_flops(c, p + 1) for p in positions))


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward (three times the forward) of one token of a
    causal sequence of ``seq``: on average a token attends over
    ``(seq + 1) / 2`` keys."""
    return 3.0 * (2.0 * matmul_params(c) + attention_flops(c, (seq + 1) / 2))
