"""Qwen3-30B-A3B MoE — the paper's MoE testbed model (§4.1).

Every layer is sparse (``decoder_sparse_step`` 1, no ``mlp_only_layers``):
128 experts of width 768, 8 per token, no shared expert, so the dense
``intermediate_size`` (6,144) of the published config is never used.
"""
from repro.models.config import ModelConfig, dense_pattern


def config() -> ModelConfig:
    return ModelConfig(
        name="qwen3-30b-moe",
        arch_type="moe",
        n_layers=48,
        d_model=2048,
        n_heads=32,
        n_kv_heads=4,
        d_ff=768,
        vocab_size=151936,
        block_pattern=dense_pattern(48),
        head_dim=128,
        qk_norm=True,
        rope_theta=1_000_000.0,
        n_experts=128,
        top_k=8,
        moe_d_ff=768,
        source="https://huggingface.co/Qwen/Qwen3-30B-A3B/blob/main/config.json",
    )
