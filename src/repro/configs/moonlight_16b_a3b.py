"""moonlight-16b-a3b [moe]: DeepSeek-V3 block, latent attention + 64-expert MoE.

27L d=2048: layer 0 dense (MLP 11264), layers 1-26 MoE with 64 routed
experts of width 1408 (top-6, sigmoid noaux_tc router with a correction
bias, weights normalised and scaled by 2.446) and 2 shared experts.
MLA: 16 heads, no q-LoRA, kv_lora_rank 512, qk_nope/v 128, qk_rope 64.
RMSNorm eps 1e-5, rope_theta 50000, untied, vocab 163840.
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]"""

from repro.configs.base import AnalogSpec, ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab=163840,
    head_dim=128,                # qk_nope_head_dim = v_head_dim
    kv_lora_rank=512,
    qk_rope_head_dim=64,
    hidden_act="silu",
    norm_eps=1e-5,
    rope_theta=50000.0,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    router_score="sigmoid",
    routed_scaling_factor=2.446,
    n_dense_layers=1,
    dense_d_ff=11264,
    analog=AnalogSpec(enabled=True, adc_bits=5, activation="silu"),
)

SMOKE = CONFIG.replace(
    name="moonlight-16b-a3b-smoke", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, head_dim=16, kv_lora_rank=32, qk_rope_head_dim=8,
    d_ff=32, dense_d_ff=96, vocab=256, n_experts=8, top_k=2,
    n_shared_experts=1, vocab_pad_multiple=8,
)
