"""Grouped-query attention (llama family; granite-8b), plain float32.

q, k, v = h Wq, h Wk, h Wv; rotary embedding on q and k; query head i reads
key/value head i // (heads / kv_heads); softmax(q k^T / sqrt(head_dim)) v
under a causal mask; then the output projection Wo.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from bench.lib.weights import Leaf
from bench.reference.common import causal_attention, ein, rope


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def leaves(c: dict) -> dict[str, Leaf]:
    d, h, kv, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    return {
        "attn.wq": Leaf((d, h, hd), "matrix", (0,)),
        "attn.wk": Leaf((d, kv, hd), "matrix", (0,)),
        "attn.wv": Leaf((d, kv, hd), "matrix", (0,)),
        "attn.wo": Leaf((h, hd, d), "matrix", (0, 1)),
    }


def attention(c: dict, lw: dict, h, positions):
    rep = c["num_attention_heads"] // c["num_key_value_heads"]
    theta = float(c["rope_theta"])
    q = rope(ein("sd,dhk->shk", h, lw["attn.wq"]), positions, theta)
    k = rope(ein("sd,dhk->shk", h, lw["attn.wk"]), positions, theta)
    v = ein("sd,dhk->shk", h, lw["attn.wv"])
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    out = causal_attention(q, k, v, 1.0 / np.sqrt(head_dim(c)))
    return ein("shk,hkd->sd", out, lw["attn.wo"])
