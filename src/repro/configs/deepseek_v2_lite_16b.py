"""deepseek-v2-lite-16b [moe] — 27L d_model=2048 16H MLA(kv_lora=512), a
dense layer 0 of width 10944, then 26 layers of 64 routed experts top-6 of
width 1408 + 2 shared; YaRN rope (factor 40).  [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite config.json]

Note: the assignment line mentions "160 routed" which is DeepSeek-V2-*full*;
the named model V2-Lite has 64 routed + 2 shared (HF config), which we follow
(also consistent with the line's own "MoE 64e top-6").  Recorded in DESIGN.md.

Routing is dropless (capacity factor n_experts / top_k gives every expert a
slot for every token) and, as published, softmax over all experts with the
top-6 probabilities left unnormalised (``norm_topk_prob`` false,
``routed_scaling_factor`` 1).
"""
from repro.core.config import MLAConfig, MoEConfig, ModelConfig, YaRNConfig

FULL = ModelConfig(
    name="deepseek_v2_lite_16b",
    family="moe",
    n_layers=27,
    n_dense_layers=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102_400,
    activation="swiglu",
    rope_theta=10_000.0,
    yarn=YaRNConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_ff_expert=1408,
                  capacity_factor=64 / 6, norm_topk_prob=False,
                  routed_scaling_factor=1.0),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0,
                  qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128),
)

SMOKE = ModelConfig(
    name="deepseek_v2_lite_smoke",
    family="moe",
    n_layers=3,
    n_dense_layers=1,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=96,
    vocab=256,
    activation="swiglu",
    yarn=YaRNConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=8, top_k=2, n_shared=1, d_ff_expert=48,
                  capacity_factor=8 / 2, norm_topk_prob=False,
                  routed_scaling_factor=1.0),
    mla=MLAConfig(kv_lora_rank=32, q_lora_rank=0,
                  qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16),
)
