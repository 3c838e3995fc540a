"""Mellum2-12B-A2.5B — JetBrains' code model: sparse experts in every layer
(64 routed experts 896 wide, softmax router, top-8 renormalised, no shared
expert), three sliding-window layers (1,024 keys) to one full, YaRN rope on
the full layers and the plain rope on the sliding ones, both at theta
500,000. [hf:JetBrains/Mellum2-12B-A2.5B-Instruct, config.json]"""

from repro.config import ModelConfig, MoEConfig, YarnScaling

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=7168,                 # published intermediate_size; no layer is dense
    vocab_size=98304,
    rope_theta=500000.0,
    rope_scaling=YarnScaling(factor=16.0, original_max_positions=8192,
                             beta_fast=32.0, beta_slow=1.0,
                             attention_factor=1.2772588722239782),
    block_pattern=("local", "local", "local", "attn"),
    sliding_window=1024,
    moe=MoEConfig(num_experts=64, experts_per_token=8, d_ff_expert=896),
    mlp_activation="silu",
    norm="rmsnorm",
)


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="mellum2-smoke",
        family="moe",
        num_layers=4,
        d_model=64,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        d_ff=128,
        vocab_size=256,
        rope_theta=500000.0,
        rope_scaling=YarnScaling(factor=16.0, original_max_positions=32,
                                 beta_fast=32.0, beta_slow=1.0,
                                 attention_factor=1.2772588722239782),
        block_pattern=("local", "local", "local", "attn"),
        sliding_window=16,
        moe=MoEConfig(num_experts=8, experts_per_token=2, d_ff_expert=32),
        mlp_activation="silu",
        norm="rmsnorm",
    )
