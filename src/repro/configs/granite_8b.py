"""granite-8b — dense llama-arch code model. [arXiv:2405.04324; hf]

36L d_model=4096 32H (GQA kv=8) d_ff=14336 vocab=49152.
Source: arXiv:2405.04324 and the ``ibm-granite/granite-8b-code-base`` model card.
"""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-8b",
    family="dense",
    n_layers=36,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    vocab_size=49152,
    mlp="swiglu",
    attn="gqa",
    rope_theta=10_000_000.0,
    microbatches=16,
)

# One chip's share: one stage of a two-stage pipeline deployment.  The
# whole model in bf16 is 16.5 GB, more than a 16 GiB chip holds, so an 8B
# model is served on such chips as two pipeline stages of 18 layers each.
# The one key that changes is n_layers (36 -> 18); every width stays as
# published.  Parameters: 18 x 436 MB + 805 MB embed/unembed = 8.66 GB.
ONE_CHIP = CONFIG.replace(n_layers=18)

REDUCED = CONFIG.replace(
    microbatches=1,
    name="granite-8b-reduced",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    vocab_size=256,
    max_seq=256,
)
