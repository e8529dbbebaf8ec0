"""Multi-head latent attention (MiniCPM3 / DeepSeek-V2), plain float32, in
its expanded (not absorbed) form.

    cq = rmsnorm(h W_dq);   q = cq W_uq = [q_nope, q_rope];  q_rope <- rope
    c  = rmsnorm(h W_dkv);  k_rope = rope(h W_kr), one per token, all heads
    k  = [c W_uk, k_rope];  v = c W_uv
    out = softmax(q k^T / sqrt(nope + rope)) v under a causal mask, then W_o
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from bench.lib.weights import Leaf
from bench.reference.common import causal_attention, ein, rmsnorm, rope


def leaves(c: dict) -> dict[str, Leaf]:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qlr, kvlr = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rd, vd = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return {
        "attn.w_dq": Leaf((d, qlr), "matrix", (0,)),
        "attn.q_norm": Leaf((qlr,), "norm"),
        "attn.w_uq": Leaf((qlr, h, nope + rd), "matrix", (0,)),
        "attn.w_dkv": Leaf((d, kvlr), "matrix", (0,)),
        "attn.kv_norm": Leaf((kvlr,), "norm"),
        "attn.w_kr": Leaf((d, rd), "matrix", (0,)),
        "attn.w_uk": Leaf((kvlr, h, nope), "matrix", (0,)),
        "attn.w_uv": Leaf((kvlr, h, vd), "matrix", (0,)),
        "attn.wo": Leaf((h, vd, d), "matrix", (0, 1)),
    }


def attention(c: dict, lw: dict, h, positions):
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    nope, rd = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    n_heads = c["num_attention_heads"]
    cq = rmsnorm(ein("sd,dr->sr", h, lw["attn.w_dq"]), lw["attn.q_norm"], eps)
    q = ein("sr,rhk->shk", cq, lw["attn.w_uq"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions, theta)], axis=-1)
    ckv = rmsnorm(ein("sd,dr->sr", h, lw["attn.w_dkv"]), lw["attn.kv_norm"], eps)
    k_rope = rope(ein("sd,dk->sk", h, lw["attn.w_kr"]), positions, theta)
    k_nope = ein("sr,rhk->shk", ckv, lw["attn.w_uk"])
    k_rope = jnp.broadcast_to(k_rope[:, None, :], (k_rope.shape[0], n_heads, rd))
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    v = ein("sr,rhk->shk", ckv, lw["attn.w_uv"])
    out = causal_attention(q, k, v, 1.0 / np.sqrt(nope + rd))
    return ein("shk,hkd->sd", out, lw["attn.wo"])
