"""deepseek-v2-lite [moe]: 27L d_model=2048 16H multi-head latent attention
(kv_lora_rank 512, no q_lora, qk 128 nope + 64 rope, v 128), YaRN RoPE
(factor 40 over 4096), one leading dense SwiGLU layer (d_ff 10944), then 26
MoE layers of 64 routed experts (width 1408, top-6 softmax, weights not
renormalised) plus 2 shared, vocab=102400.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400,
    ffn="moe", num_experts=64, experts_per_token=6, moe_d_ff=1408,
    shared_experts=2, norm_topk_prob=False,
    # the sequence-wise balance factor DeepSeek-V2 gives for V2-Lite
    router_aux_coef=0.001, first_dense_layers=1,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0, rope_factor=40.0, rope_original_max_pos=4096,
    rope_mscale_all_dim=0.707,
    rules="tp", remat_policy="full",
)


def tiny() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-tiny", family="moe",
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        d_ff=96, vocab_size=256,
        ffn="moe", num_experts=16, experts_per_token=3, moe_d_ff=32,
        shared_experts=2, norm_topk_prob=False, router_aux_coef=0.001,
        first_dense_layers=1,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16,
        rope_factor=40.0, rope_original_max_pos=64, rope_mscale_all_dim=0.707,
        dtype="float32", rules="tp", remat_policy="none",
    )
