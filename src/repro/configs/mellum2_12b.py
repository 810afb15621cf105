"""Mellum2-12B-A2.5B — 64-expert top-8 MoE under GQA attention whose layers
repeat (sliding, sliding, sliding, full): 1,024-token windows with plain
RoPE, full attention with YaRN.
[https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json]
"""
from repro.configs.base import ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    citation=("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct"
              "/blob/main/config.json"),
    num_layers=28,
    d_model=2304,
    num_heads=32,
    num_kv_heads=4,          # GQA kv=4; query width 4,096 != d_model
    head_dim=128,
    d_ff=7168,               # published intermediate_size; no layer uses it
    vocab_size=98304,
    gated_ffn=True,          # SwiGLU experts
    norm_eps=1e-6,
    rope_theta=500_000.0,
    sliding_window=1024,
    yarn=YarnConfig(factor=16.0, original_max_position_embeddings=8192,
                    beta_fast=32.0, beta_slow=1.0,
                    attention_factor=1.2772588722239782),
    moe=MoEConfig(num_experts=64, top_k=8, d_expert=896),
    pattern=(("attn", "moe", "sliding"),) * 3 + (("attn", "moe", "full"),),
)
