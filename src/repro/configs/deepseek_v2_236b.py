"""deepseek-v2-236b — MLA (kv_lora 512) + MoE 160 routed top-6, 2 shared.
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2 config.json]  Routing is
group-limited (the best 3 of 8 groups, a group scored by its best
expert), not renormalised, scaled by 16; the rope is YaRN (factor 40
over 4,096 original positions).  Optimizer: adafactor (memory: 236B
params on 16 GB/chip v5e forces a factored second moment)."""

from repro.models.config import (MLAConfig, ModelConfig, MoEConfig,
                                 YarnScaling)

YARN = YarnScaling(factor=40, original_max_position_embeddings=4096,
                   beta_fast=32, beta_slow=1, mscale=0.707,
                   mscale_all_dim=0.707)

CONFIG = ModelConfig(
    name="deepseek-v2-236b", family="moe",
    n_layers=60, d_model=5120, n_heads=128, n_kv_heads=128,
    d_ff=1536, vocab=102400, activation="swiglu",
    rope_theta=10000.0, rope_scaling=YARN, norm_eps=1e-6,
    max_seq=32768, optimizer="adafactor",
    moe=MoEConfig(n_experts=160, top_k=6, n_shared=2,
                  d_ff_expert=1536, d_ff_shared=3072,
                  first_dense_layers=1, d_ff_dense=12288,
                  topk_method="group_limited_greedy", n_group=8,
                  topk_group=3, norm_topk_prob=False,
                  routed_scaling_factor=16.0),
    mla=MLAConfig(kv_lora=512, q_lora=1536, rope_head_dim=64,
                  nope_head_dim=128, v_head_dim=128),
)

SMOKE = ModelConfig(
    name="deepseek-v2-236b-smoke", family="moe",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=96, vocab=512, activation="swiglu", max_seq=256,
    rope_scaling=YarnScaling(factor=40, original_max_position_embeddings=64,
                             beta_fast=32, beta_slow=1, mscale=0.707,
                             mscale_all_dim=0.707),
    optimizer="adafactor",
    moe=MoEConfig(n_experts=4, top_k=2, n_shared=1,
                  d_ff_expert=64, d_ff_shared=64,
                  first_dense_layers=1, d_ff_dense=128,
                  capacity_factor=4.0, topk_method="group_limited_greedy",
                  n_group=2, topk_group=1, norm_topk_prob=False,
                  routed_scaling_factor=16.0),
    mla=MLAConfig(kv_lora=32, q_lora=48, rope_head_dim=8,
                  nope_head_dim=16, v_head_dim=16),
    remat="none",
)
