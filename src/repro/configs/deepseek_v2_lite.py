"""deepseek-v2-lite [moe, latent attention]: 27L d_model=2048 16H MLA
(kv_lora_rank 512, nope 128 + rope 64, v 128, no q-LoRA) vocab=102400.

Layer 0 dense (SwiGLU 10944); layers 1-26 route top-6 of 64 experts
(width 1408, softmax router, weights not renormalised) plus 2 shared
experts (one SwiGLU of 2816). YaRN rope: factor 40 over 4096 original
positions, beta 32/1, mscale 0.707/0.707. 15.7B parameters, 2.4B active.
[hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434]
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,
    moe_d_ff=1408,
    vocab_size=102400,
    max_seq_len=163840,
    n_experts=64,
    experts_per_token=6,
    moe_dropless=True,
    n_shared_experts=2,
    first_k_dense=1,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
)
