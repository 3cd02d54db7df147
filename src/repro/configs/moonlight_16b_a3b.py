"""moonlight-16b-a3b-ep8 [mla_moe] — Moonlight-16B-A3B (DeepSeek-V3
architecture) as one chip's share of an 8-way expert-parallel deployment.

Published (config.json of moonshotai/Moonlight-16B-A3B, model_type
deepseek_v3): hidden 2,048; 27 layers, the first dense (width 11,264), then
26 expert layers; latent attention with 16 heads, no query compression,
kv_lora_rank 512, qk_nope 128, qk_rope 64, v 128, RoPE θ=50,000 unscaled;
64 routed experts of width 1,408, 6 per token, chosen by sigmoid score plus
a correction bias (noaux_tc, one group), normalised and scaled by 2.446;
2 shared experts; RMSNorm ε 1e-5; untied head over 163,840 words.

The deployment: 8 chips share each expert layer, each holding 8 of the 64
routed experts (this chip: experts 0–7); attention, the dense layer, the
router and the shared experts are held whole on every chip. Depth is one
pipeline stage: the dense layer and the first 8 expert layers (9 of 27).
Every width is published.
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b-ep8",
    family="mla_moe",
    num_layers=9,
    first_k_dense=1,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    d_ff=11264,
    vocab_size=163_840,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    mlp_activation="swiglu",
    positional="rope",
    rope_theta=50_000.0,
    tie_embeddings=False,
    norm="rmsnorm",
    norm_eps=1e-5,
    moe=MoEConfig(num_experts=64, top_k=6, routed_scale=2.446, d_ff=1408,
                  num_shared=2, held=8, held_offset=0),
    dtype="bfloat16",
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
           "config.json (arXiv:2405.04434 §2.1 MLA; arXiv:2412.19437 "
           "§2.1.2 routing)",
)
