"""Qwen3-30B-A3B — 128 experts top-8 [hf:Qwen/Qwen3-30B-A3B; hf].

48L d_model=2048 32H (GQA kv=4, head_dim 128) per-expert d_ff=768
vocab=151936; QK-norm, rope_theta 1e6, rms_norm_eps 1e-6, untied
embeddings; every layer attention + MoE (intermediate_size 6144 unused).
"""
from repro.configs.base import ModelConfig, register


@register
def qwen3_moe_30b_a3b() -> ModelConfig:
    return ModelConfig(
        name="qwen3-moe-30b-a3b",
        family="moe",
        d_model=2048,
        n_heads=32,
        n_kv_heads=4,
        d_ff=6144,
        n_layers=48,
        vocab_size=151936,
        layout=(((("attn", "moe"),), 48),),
        n_experts=128,
        top_k=8,
        moe_dff=768,
        head_dim=128,
        qk_norm=True,
        rope_theta=1e6,
        norm_eps=1e-6,
        tie_embeddings=False,
        supports_long_context=False,
    )
