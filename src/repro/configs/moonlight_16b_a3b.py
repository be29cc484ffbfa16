"""Moonlight-16B-A3B — DeepSeek-V3 block: latent attention (MLA) and 64
routed experts (top-6, sigmoid scores with a selection-only bias) plus 2
shared experts, after one dense layer.
[hf:moonshotai/Moonlight-16B-A3B config.json]"""
from repro.configs.base import MOE, ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    arch_type=MOE,
    citation="hf:moonshotai/Moonlight-16B-A3B",
    n_layers=27,
    first_dense_layers=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=11264,           # the dense layer's SwiGLU width
    moe_d_ff=1408,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,   # fused: one always-on SwiGLU of width 2,816
    router_score="sigmoid",
    routed_scaling=2.446,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    vocab_size=163840,
    rope_theta=50_000.0,
    max_seq_len=8192,
)
