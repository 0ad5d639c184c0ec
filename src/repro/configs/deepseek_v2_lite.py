"""deepseek-v2-lite [moe] — latent attention (MLA) and fine-grained MoE.

27L d=2048 16H, no query compression, kv_lora_rank 512, qk 128 + 64 rope,
v 128; layer 0 a dense SiLU MLP of 10944, layers 1-26 64 routed experts of
1408 (softmax top-6, gates not renormalized, routed_scaling_factor 1) plus
2 shared; YaRN x40 over 4096; vocab 102400, untied head.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]
"""
from .base import MLAConfig, ModelConfig, MoEConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,           # qk_nope_head_dim + qk_rope_head_dim
    d_ff=10_944,            # the leading dense layer
    vocab_size=102_400,
    block_pattern=("mla",),
    act="silu",
    rope_theta=10_000.0,
    first_k_dense=1,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128,
                  rope_scaling=YarnScaling(
                      factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707)),
    # the published config sets no capacity: every routed token is served
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  capacity_factor=None, num_shared_experts=2,
                  norm_topk_prob=False),
)
