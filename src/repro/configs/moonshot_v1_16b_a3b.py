"""moonshot-v1-16b-a3b [moe] — hf:moonshotai/Moonlight-16B-A3B (deepseek_v3).

27L d_model=2048 16H, latent attention (MLA: kv_lora_rank 512, q_lora_rank
null, qk_nope 128 + qk_rope 64, v 128), one leading dense layer of d_ff
11264, then 26 MoE layers of 64 routed experts (width 1408, top-6, sigmoid
scores with a selection bias, top-6 renormalised, scaled 2.446) plus 2
shared experts; vocab 163840, untied, rope_theta 50000."""

import dataclasses

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab=163840,
    act="silu",
    glu=True,
    rope_theta=50000.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64,
    top_k=6,
    n_shared_experts=2,
    first_k_dense_replace=1,
    dense_d_ff=11264,
    scoring_func="sigmoid",
    router_bias=True,
    norm_topk_prob=True,
    routed_scaling_factor=2.446,
)

SMOKE = dataclasses.replace(
    CONFIG, name="moonshot-v1-smoke", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_head=0, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, d_ff=48, dense_d_ff=96, vocab=256,
    n_experts=8, top_k=2, n_shared_experts=1, dtype="float32", remat=False)
