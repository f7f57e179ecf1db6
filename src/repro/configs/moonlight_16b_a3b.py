"""moonlight-16b-a3b [moe] — deepseek_v3: latent attention (MLA, kv_lora
512, no q_lora), one dense layer then 26 MoE layers of 64 routed experts
(sigmoid scores, noaux_tc bias, top-6, scaled 2.446) + 2 shared.
[hf:moonshotai/Moonlight-16B-A3B]"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe", n_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=11264, vocab=163840,
    rope_theta=50_000.0, norm_eps=1e-5,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, n_dense_layers=1,
    n_experts=64, experts_per_tok=6, moe_d_ff=1408, n_shared_experts=2,
    router="sigmoid", routed_scaling=2.446, capacity_factor=0.0,
    norm="rmsnorm", act="swiglu")
