"""mellum2-12b-a2.5b [moe] — 64 fine-grained experts top-8 (softmax router,
top-k renormalised), no shared expert; GQA 32/4 heads of 128; three
sliding-window (1024) layers then one full layer with YaRN rotary.

[hf:JetBrains/Mellum2-12B-A2.5B-Instruct]
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b", family="moe",
    num_layers=28, d_model=2304, num_heads=32, num_kv_heads=4, head_dim=128,
    d_ff=896, vocab_size=98304, tie_embeddings=False,
    num_experts=64, experts_per_token=8,
    sliding_window=1024, layer_types=("sliding", "sliding", "sliding", "full"),
    rope_theta=500000.0, norm_eps=1e-6,
    yarn_factor=16.0, yarn_original_max_position=8192,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
)

#: One chip's share of a deployment that splits each layer over 8 chips
#: by expert parallelism (attention and the router replicated) and runs
#: the layers as pipeline stages of one period: the first stage's 4
#: layers, experts 0-7 of each, vocabulary rows 0-12,287.  340,349,184
#: parameters; every width as published.
CHIP_SHARE = CONFIG.replace(num_layers=4, experts_held=8, vocab_size=12288)
