"""minicpm3-4b — dense with MLA (multi-head latent attention).
[hf:openbmb/MiniCPM3-4B]

62L d_model=2560 40H d_ff=6400 vocab=73448.
MLA ranks per the HF config: q_lora 768, kv_lora 256, qk_nope 64, qk_rope 32,
v_head 64.  The decode path uses the absorbed form, so the per-token cache
is (kv_lora_rank + rope_dim) = 288 values a layer.

MiniCPM's muP scalings, from the published ``scale_emb`` 12,
``scale_depth`` 1.4 and ``dim_model_base`` 256: embeddings x 12, each
attention and MLP branch x 1.4 / sqrt(62) before its residual add, and the
final hidden state x 256 / 2560 before the LM head.  The residual scale
keeps the published depth in a config cut to fewer layers.  LongRoPE
(``rope_scaling``) is not applied: rotary embeddings are plain.
"""

import math

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="minicpm3-4b",
    family="dense",
    n_layers=62,
    d_model=2560,
    n_heads=40,
    n_kv_heads=40,
    d_ff=6400,
    vocab_size=73448,
    mlp="swiglu",
    attn="mla",
    q_lora_rank=768,
    kv_lora_rank=256,
    qk_nope_dim=64,
    qk_rope_dim=32,
    v_head_dim=64,
    embed_scale=12.0,
    residual_scale=1.4 / math.sqrt(62),
    logit_scale=256 / 2560,
    microbatches=16,
    # §Perf A1: 40 heads don't divide the 16-way model axis -> attention
    # would replicate 16x; shard the sequence dim instead (sequence
    # parallelism for the uneven-head archs — see EXPERIMENTS.md §Perf)
    sharding_overrides={"seq": "model"},
)

REDUCED = CONFIG.replace(
    sharding_overrides=None,
    microbatches=1,
    name="minicpm3-4b-reduced",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=128,
    vocab_size=256,
    q_lora_rank=32,
    kv_lora_rank=16,
    qk_nope_dim=16,
    qk_rope_dim=8,
    v_head_dim=16,
    max_seq=256,
)
