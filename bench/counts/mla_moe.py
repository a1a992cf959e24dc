"""Operations of the latent-attention MoE decoder (DeepSeek-V2), one
chip's expert share, from its configuration file.

Model operations only: what the algorithm needs, not what an
implementation computes.  The routed experts count at the share of a
token's ``num_experts_per_tok`` choices that lands on the held experts
on average, ``held / published experts``: what the chip's experts add,
however many rows the implementation multiplies.  Attention counts in
its absorbed form, which decode needs (no key or value is decompressed
per cached position): each head scores a query of ``kv_lora_rank +
qk_rope_head_dim`` against each key and sums ``kv_lora_rank`` values.
A multiply-add counts two.
"""

from __future__ import annotations

from bench.arch.mla_moe import experts


def attn_params(c: dict) -> int:
    """Weights a token multiplies in one layer's latent attention: the
    query's down and up projections, the latent's, the key and value
    up-projections absorbed into the query and the output, the output."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return (d * ql + ql * H * (nope + rope) + d * (kl + rope)
            + kl * H * nope + kl * H * v + H * v * d)


def matmul_params(c: dict) -> float:
    """Weights every token multiplies, on average: attention in every
    layer, the dense layers' FFN, each MoE layer's router, shared experts
    and its expected share of routed experts, and the output head."""
    d = c["hidden_size"]
    L, n_dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    total, _, held = experts(c)
    expert = 3 * d * c["moe_intermediate_size"]
    moe = (d * total + c["n_shared_experts"] * expert
           + c["num_experts_per_tok"] * held / total * expert)
    return (L * attn_params(c) + n_dense * 3 * d * c["intermediate_size"]
            + (L - n_dense) * moe + d * c["vocab_size"])


def attention_flops(c: dict, context: float) -> float:
    """Absorbed-form score and value products of one query token against
    ``context`` keys, over all layers."""
    return (2.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * (2 * c["kv_lora_rank"] + c["qk_rope_head_dim"]) * context)


def decode_flops(c: dict, positions) -> float:
    """One decode token per entry of ``positions`` (the token's index in
    its sequence; it attends over ``position + 1`` keys)."""
    return (2.0 * matmul_params(c) * len(positions)
            + sum(attention_flops(c, p + 1) for p in positions))
