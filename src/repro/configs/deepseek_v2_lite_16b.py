"""DeepSeek-V2-Lite (16B) — MLA attention + fine-grained MoE.

[https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json,
arXiv:2405.04434]
27L d_model=2048 16H, MLA kv_lora_rank=512 without a q projection rank
(qk_nope=128, qk_rope=64, v_head=128), YaRN rope (factor 40 over an
original 4096 positions, mscale = mscale_all_dim = 0.707, θ = 10⁴), MoE:
2 shared + 64 routed experts, top-6 by softmax score (greedy, not
renormalised), d_expert=1408, first layer dense (d_ff=10944),
vocab=102400, untied head, RMSNorm eps 1e-6 (``apply_norm``'s, as every
config here has it).

The 64 routed experts are V2-Lite's own count (the full DeepSeek-V2 has
160). The published rope layout interleaves the rotary channels of
q_pe/k_pe; the program rotates halves, which is the same model with those
weight columns permuted (a score is a dot product over the channels, so
permuting them in q and k alike changes nothing).
"""
from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                RopeScaling, register)

CONFIG = register(ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
           "config.json",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=0,                 # MLA defines its own head dims
    d_ff=10944,                 # dense FFN for the first layer
    vocab_size=102400,
    activation="swiglu",
    norm="rmsnorm",
    rope_theta=10000.0,
    rope_scaling=RopeScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    max_position_embeddings=163840,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_expert=1408,
                  num_shared_experts=2, d_shared=1408,
                  router_aux_weight=0.001, first_dense=1),
))
