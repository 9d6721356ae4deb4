"""deepseek-v2-lite — DeepSeek-V2 [arXiv:2405.04434]; the paper's primary
MoE backbone (shared experts + MLA), as published in
hf:deepseek-ai/DeepSeek-V2-Lite (config.json).

27 layers, d_model=2048, 16 heads of MLA: kv_lora_rank=512, no query
compression, per-head query/key 128 (no rope) + 64 (rope, one key shared
by every head), values 128; YaRN rope scaling (factor 40 over 4096
positions, mscale 0.707). Layer 0 is dense (SwiGLU of 10944); layers
1-26 route over 64 experts of 1408, top-6, softmax weights not
renormalized, plus two shared experts (one SwiGLU of 2816).
Vocabulary 102400, untied.
"""
from repro.configs.base import ModelConfig, MoEConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    ffn_dim=10944,
    vocab_size=102400,
    attention="mla",
    rope_theta=10000.0,
    rope_scaling=YarnScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    norm_eps=1e-6,
    mla_kv_lora_rank=512,
    mla_rope_dim=64,
    mla_v_dim=128,
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        expert_ffn_dim=1408,
        num_shared_experts=2,
        shared_ffn_dim=1408,
        moe_layer_start=1,
        norm_topk_prob=False,
    ),
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
)


def smoke():
    return CONFIG.reduced()
