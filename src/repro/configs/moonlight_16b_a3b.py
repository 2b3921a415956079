"""moonlight-16b-a3b [moe]: 27L d_model=2048 16H MLA, vocab=163840.

DeepSeek-V3 block (huggingface.co/moonshotai/Moonlight-16B-A3B, config.json,
model_type deepseek_v3): latent attention on every layer (kv_lora_rank 512,
no query LoRA, qk heads 128 + 64 RoPE, v heads 128); layer 0 a dense SwiGLU
of width 11264, layers 1-26 hold 64 routed SwiGLU experts of width 1408, 6
per token, and 2 shared experts; sigmoid scores with a correction bias that
picks the experts but does not weight them, top-k weights renormalised and
scaled by 2.446; RoPE theta 50000, RMSNorm eps 1e-5, untied head.
"""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,                 # per-expert hidden
    vocab_size=163_840,
    mlp_kind="swiglu",
    norm_kind="rmsnorm",
    rope_theta=50_000.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    norm_eps=1e-5,
    moe=MoEConfig(num_experts=64, top_k=6, num_shared_experts=2,
                  layout="all_but_first", scoring="sigmoid",
                  routed_scaling=2.446, dense_d_ff=11_264, aux_weight=1e-4,
                  z_weight=0.0),
)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b-smoke",
        family="moe",
        num_layers=3,
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        d_ff=32,
        vocab_size=256,
        mlp_kind="swiglu",
        norm_kind="rmsnorm",
        rope_theta=50_000.0,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        norm_eps=1e-5,
        moe=MoEConfig(num_experts=8, top_k=3, num_shared_experts=2,
                      layout="all_but_first", num_routed_experts=16,
                      scoring="sigmoid",
                      routed_scaling=2.446, dense_d_ff=96, aux_weight=1e-4,
                      z_weight=0.0),
        dtype="float32",
    )
