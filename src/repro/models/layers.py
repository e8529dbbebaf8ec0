"""Layers shared across the architecture families: rotary embeddings, the
MLP variants, and the token embedding and LM head.

Everything is a pure function over parameter pytrees built from
:class:`repro.distributed.sharding.TensorSpec` templates.  The attention
and RMSNorm the models run are in :mod:`repro.kernels.ref`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import TensorSpec, shard
from repro.kernels.ref import NEG_INF

# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_cos_sin(positions: jax.Array, dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """positions: (..., S) int32 -> cos/sin of shape (..., S, dim//2)."""
    inv_freq = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, H, D). cos/sin: broadcastable (..., S, 1, D//2)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dtype)


def rope_for(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """Convenience: (B, S) positions -> (B, S, 1, D//2) cos/sin for heads."""
    cos, sin = rope_cos_sin(positions, head_dim, theta)
    return cos[:, :, None, :], sin[:, :, None, :]


# ---------------------------------------------------------------------------
# MLP variants
# ---------------------------------------------------------------------------


def mlp_template(cfg) -> dict[str, TensorSpec]:
    d, f = cfg.d_model, cfg.d_ff
    t = {
        "w_up": TensorSpec((d, f), ("d_model", "d_ff"), dtype=cfg.dtype),
        "w_down": TensorSpec((f, d), ("d_ff", "d_model"), dtype=cfg.dtype),
    }
    if cfg.gated_mlp:
        t["w_gate"] = TensorSpec((d, f), ("d_model", "d_ff"), dtype=cfg.dtype)
    return t


def mlp_forward(params: dict, x: jax.Array, cfg) -> jax.Array:
    """x: (..., d_model)."""
    up = x @ params["w_up"]
    if cfg.mlp == "swiglu":
        gate = x @ params["w_gate"]
        hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    elif cfg.mlp == "relu2":  # nemotron squared-ReLU
        r = jax.nn.relu(up)
        hidden = r * r
    elif cfg.mlp == "gelu":
        hidden = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    else:
        raise ValueError(cfg.mlp)
    hidden = shard(hidden, "batch", "seq", "act_d_ff")
    return hidden @ params["w_down"]


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------


def embedding_template(cfg) -> dict[str, TensorSpec]:
    pv = cfg.padded_vocab_size
    t = {"tok": TensorSpec((pv, cfg.d_model), ("vocab", "d_model"), dtype=cfg.dtype)}
    if not cfg.tie_embeddings:
        t["unembed"] = TensorSpec(
            (cfg.d_model, pv), ("d_model", "vocab"), dtype=cfg.dtype
        )
    return t


def vocab_mask_logits(logits: jax.Array, cfg) -> jax.Array:
    """-inf the padded vocab tail so softmax/argmax ignore it."""
    pv = cfg.padded_vocab_size
    if pv == cfg.vocab_size:
        return logits
    valid = jnp.arange(pv) < cfg.vocab_size
    return jnp.where(valid, logits, NEG_INF)


def embed_tokens(params: dict, tokens: jax.Array, cfg) -> jax.Array:
    out = jnp.take(params["tok"], tokens, axis=0)
    if cfg.embed_scale != 1.0:
        out = out * cfg.embed_scale
    return shard(out, "batch", "seq", "act_d_model")


def unembed(params: dict, x: jax.Array, cfg) -> jax.Array:
    w = params["tok"].T if cfg.tie_embeddings else params["unembed"]
    if cfg.logit_scale != 1.0:
        x = x * cfg.logit_scale
    return x @ w
