"""DeepSeek-V2-Lite — latent attention (MLA) and DeepSeekMoE, as one chip of
an eight-chip expert-parallel deployment.  [arXiv:2405.04434 §2.1-2.2; hf
deepseek-ai/DeepSeek-V2-Lite config.json]

Every width is published: 27 layers, d 2048, 16 heads with no q_lora
(q_proj 2048 -> 16 x (128 + 64)), kv_lora_rank 512 plus a 64-wide rotary key
shared by the heads, v 128; YaRN on the rotary dims (factor 40 over 4096
positions); layer 0 a dense SwiGLU of 10944, layers 1-26 a 64-way softmax
router, top-6 without renormalisation, experts of 1408 and two shared experts
(one SwiGLU of 2816); untied 102400-row head.

The cut: each MoE layer's 64 experts are split over 8 chips, and this chip
holds experts 0-7 (``experts_held``).  The router keeps its 64 outputs and
top-6; the layer computes its own experts' part of the routed result.
Attention, the shared experts, layer 0 and the vocabulary are whole.
"""
from .base import ModelConfig, register

DEEPSEEK_V2_LITE = register(ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=10944,
    vocab_size=102400,
    rope_theta=10_000.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    yarn_factor=40.0,
    yarn_original_max_position=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    first_dense_layers=1,
    num_experts=64,
    num_experts_per_tok=6,
    moe_d_ff=1408,
    shared_expert_d_ff=2816,
    experts_held=8,
    expert_offset=0,
    moe_norm_topk=False,
    norm_eps=1e-6,
))
