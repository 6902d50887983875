"""moonlight-16b-a3b [moe]: 27L d=2048 16H, latent attention (MLA:
kv_lora_rank 512, qk 128 + 64 rotary, v 128), 64 routed experts of 1408
(top 6, sigmoid scores with a correction bias, renormalised and scaled by
2.446) and 2 shared experts; the first layer dense (d_ff 11264);
vocab=163840, untied.

The DeepSeek-V3 block (model_type deepseek_v3) without multi-token
prediction: q_lora_rank null, n_group and topk_group 1, no rope_scaling.
[hf:moonshotai/Moonlight-16B-A3B; config.json]
"""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b", family="moe",
        n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128,
        d_ff=1408, vocab=163840,
        n_experts=64, top_k=6, n_shared_experts=2, first_dense_layers=1,
        dense_ff=11264,
        router_score="sigmoid", router_bias=True,
        routed_scaling_factor=2.446,
        activation="silu", gated_mlp=True,
        rope_theta=5e4, max_seq=8192, norm_eps=1e-5,
        param_dtype="bfloat16", compute_dtype="bfloat16",
    )


def smoke_config() -> ModelConfig:
    return config().scaled(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16,
        d_ff=32, dense_ff=128, vocab=256, max_seq=128,
        n_experts=8, top_k=2, n_shared_experts=2, first_dense_layers=1,
        param_dtype="float32", compute_dtype="float32",
    )
