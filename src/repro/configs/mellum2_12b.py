"""mellum2-12b — JetBrains Mellum2-12B-A2.5B: sparse experts on every layer,
three sliding-window layers to one full-attention layer, and a rotary per
kind of layer [hf:JetBrains/Mellum2-12B-A2.5B-Instruct, config.json].

28L, d_model=2304, 32 heads (GQA kv=4) of head_dim 128 (q_dim 4096 !=
d_model), no attention bias; ``layer_types`` sliding x3 then full, seven
times, window 1024; sliding layers rotate with plain RoPE (theta 5e5), full
layers with YaRN (theta 5e5, factor 16 over 8,192 original positions, beta
32/1, attention factor 1.2772588722239782); every FFN is 64 experts of
width 896, top-8 with the gates renormalised, no shared expert; vocab
98,304, untied; RMSNorm eps 1e-6.  12.15 B parameters.  The MTP head that
``described_as`` mentions is not in config.json and never runs when serving
without speculation, so it is left out.

``EP8`` is one chip's share of an 8-way expert-parallel deployment: each
layer holds experts 0-7 of the 64 (the router keeps its 64 outputs and
top-8, and the layer adds only its own experts' gated outputs); attention,
embedding and head are whole on every chip.  2.44 B parameters, 4.88 GB in
bf16 — the whole model (24.3 GB) does not fit one 16 GB chip.
"""

import dataclasses

from repro.common.config import (AttentionConfig, LookaheadConfig, ModelConfig,
                                 MoEConfig, YarnConfig)

YARN = YarnConfig(factor=16.0, original_max_position=8192, beta_fast=32.0,
                  beta_slow=1.0, attention_factor=1.2772588722239782)

CONFIG = ModelConfig(
    name="mellum2-12b",
    arch_type="moe",
    num_layers=28,
    d_model=2304,
    d_ff=896,
    vocab_size=98304,
    attn=AttentionConfig(num_heads=32, num_kv_heads=4, head_dim=128,
                         rope_theta=5e5, rope_yarn=YARN, local_rope_theta=5e5,
                         sliding_window=1024, global_every=4),
    moe=MoEConfig(num_experts=64, top_k=8, d_expert=896),
    # routed experts' stacked weights take no LoRA (core/lookahead.py)
    lookahead=LookaheadConfig(lora_targets=("wq", "wk", "wv", "wo")),
    norm_eps=1e-6,
    tie_embeddings=False,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
)

EP8 = dataclasses.replace(
    CONFIG, name="mellum2-12b-ep8",
    moe=dataclasses.replace(CONFIG.moe, experts_held=8))


def smoke() -> ModelConfig:
    """Two periods of the pattern, 8 experts held of 16 (top-4), window 32."""
    return ModelConfig(
        name="mellum2-smoke", arch_type="moe", num_layers=8, d_model=128,
        d_ff=64, vocab_size=512,
        attn=AttentionConfig(num_heads=4, num_kv_heads=2, head_dim=48,
                             rope_theta=5e5, rope_yarn=YARN,
                             local_rope_theta=5e5, sliding_window=32,
                             global_every=4),
        moe=MoEConfig(num_experts=16, top_k=4, d_expert=64, experts_held=8),
        lookahead=LookaheadConfig(n_lookahead=8, lora_rank=4, window_size=8,
                                  pool_kernel=7,
                                  lora_targets=("wq", "wk", "wv", "wo")),
        tie_embeddings=False,
    )
