"""mellum2-12b-a2.5b [moe] — three sliding-window layers then one full
layer, seven times; 64 experts top-8 in every layer, no shared expert
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json].

Sliding layers: window 1,024, default RoPE at theta 500,000. Full layers:
YaRN, factor 16 over 8,192 original positions. q and k are RMS-normed over
head_dim before RoPE, as in the Qwen3-MoE lineage whose keys the config
carries (it has no key for that)."""
from .base import ArchConfig, MoEConfig, RopeConfig, smoke_variant

CONFIG = ArchConfig(
    arch_id="mellum2-12b-a2.5b", family="moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4,
    head_dim=128, d_ff=7168, vocab=98304,
    qk_norm=True, rope_theta=500_000.0, norm_eps=1e-6,
    moe=MoEConfig(n_experts=64, top_k=8, n_shared=0, d_expert=896),
    sliding_window=1024,
    layer_types=("window", "window", "window", "full"),
    rope_by_kind=(("full", RopeConfig(
        theta=500_000.0, yarn_factor=16.0, original_max_positions=8192,
        beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782)),),
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct/config.json",
)

def smoke():
    return smoke_variant(CONFIG)
