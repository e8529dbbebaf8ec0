"""Attention modules: GQA (llama/granite/qwen/grok/...) and MLA (minicpm3).

Each module provides a parameter template plus three entry points:
  * ``*_prefill``  — full-sequence attention, returns output + filled cache,
  * ``*_decode``   — one-token attention against the cache, returns output +
                     updated cache,
  * used by both train (no cache) and serve paths.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import TensorSpec, seq_sharded, shard
from repro.kernels.ref import NEG_INF, chunked_attention, decode_attention_reference, rmsnorm
from repro.models import kvcache
from repro.models.layers import apply_rope, rope_for

# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_template(cfg) -> dict[str, TensorSpec]:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    t = {
        "wq": TensorSpec((d, h, hd), ("d_model", "heads", "head_dim"), dtype=cfg.dtype),
        "wk": TensorSpec((d, kv, hd), ("d_model", "kv_heads", "head_dim"), dtype=cfg.dtype),
        "wv": TensorSpec((d, kv, hd), ("d_model", "kv_heads", "head_dim"), dtype=cfg.dtype),
        "wo": TensorSpec((h, hd, d), ("heads", "head_dim", "d_model"), dtype=cfg.dtype),
    }
    if cfg.qkv_bias:
        t["bq"] = TensorSpec((h, hd), ("heads", "head_dim"), init="zeros", dtype=cfg.dtype)
        t["bk"] = TensorSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros", dtype=cfg.dtype)
        t["bv"] = TensorSpec((kv, hd), ("kv_heads", "head_dim"), init="zeros", dtype=cfg.dtype)
    return t


def _gqa_qkv(params: dict, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = shard(q, "batch", "seq", "act_heads", None)
    k = shard(k, "batch", "seq", "act_heads", None)
    v = shard(v, "batch", "seq", "act_heads", None)
    return q, k, v


def gqa_prefill(
    params: dict,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,  # (B, S)
    cfg,
    *,
    causal: bool = True,
    use_rope: bool = True,
    cache: dict | None = None,
    kv_override: tuple[jax.Array, jax.Array] | None = None,  # cross-attention
) -> tuple[jax.Array, dict | None]:
    hd = cfg.resolved_head_dim
    q, k, v = _gqa_qkv(params, x, cfg)
    if kv_override is not None:
        k, v = kv_override
    elif use_rope:
        cos, sin = rope_for(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    if seq_sharded():
        # sequence-parallel attention (§Perf A2): q stays a local seq shard
        # (no q-chunk scan — scanning a sharded axis makes GSPMD replicate),
        # k/v are gathered once per layer (replicated over the model axis)
        q = shard(q, "batch", "seq", None, None)
        k = shard(k, "batch", None, None, None)
        v = shard(v, "batch", None, None, None)
        out = chunked_attention(q, k, v, causal=causal, q_chunk=q.shape[1])
    else:
        out = chunked_attention(q, k, v, causal=causal)
    new_cache = None
    if cache is not None and kv_override is None:
        lengths = positions[:, -1] + 1
        new_cache = kvcache.write_prompt_kv(cache, k, v, lengths)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return shard(out, "batch", "seq", "act_d_model"), new_cache


def gqa_decode(
    params: dict,
    x: jax.Array,  # (B, 1, d)
    cfg,
    cache: dict,
    *,
    use_rope: bool = True,
    cross_cache: dict | None = None,  # whisper cross-attn: static k/v, no append
) -> tuple[jax.Array, dict]:
    hd = cfg.resolved_head_dim
    q, k, v = _gqa_qkv(params, x, cfg)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]  # (B, H/KV, D)

    if cross_cache is not None:
        out = decode_attention_reference(
            q, cross_cache["k"], cross_cache["v"], cross_cache["lengths"]
        )
        new_cache = cache
    else:
        if use_rope:
            pos = cache["lengths"][:, None]  # (B, 1)
            cos, sin = rope_for(pos, hd, cfg.rope_theta)
            q = apply_rope(q[:, None], cos, sin)[:, 0]
            k = apply_rope(k[:, None], cos, sin)[:, 0]
        new_cache = kvcache.append_kv(cache, k, v)
        out = decode_attention_reference(
            q, new_cache["k"], new_cache["v"], new_cache["lengths"]
        )
    out = jnp.einsum("bhk,hkd->bd", out, params["wo"])[:, None]
    return shard(out, "batch", "seq", "act_d_model"), new_cache


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention) — minicpm3 / deepseek-v2 family
# ---------------------------------------------------------------------------
#
# q  = W_uq · rmsnorm(W_dq · x)            -> (H, nope+rope)
# c  = rmsnorm(W_dkv · x)                  -> kv_lora_rank   (cached)
# kr = rope(W_kr · x)                      -> qk_rope_dim    (cached, shared)
# k  = [W_uk · c  (per head), kr] ; v = W_uv · c
#
# Decode uses the *absorbed* form: q_nope is pushed through W_uk^T so the
# score is an inner product in latent space against the cached ``c`` directly
# — O(kv_lora_rank) per cached token instead of O(H * head_dim).  Prefill
# expands k/v (standard form) for throughput.


def mla_template(cfg) -> dict[str, TensorSpec]:
    d = cfg.d_model
    h = cfg.n_heads
    qlr, kvlr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope_d, vdim = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "w_dq": TensorSpec((d, qlr), ("d_model", "lora"), dtype=cfg.dtype),
        "q_norm": TensorSpec((qlr,), ("lora",), init="ones", dtype=cfg.dtype),
        "w_uq": TensorSpec((qlr, h, nope + rope_d), ("lora", "heads", "head_dim"), dtype=cfg.dtype),
        "w_dkv": TensorSpec((d, kvlr), ("d_model", "lora"), dtype=cfg.dtype),
        "kv_norm": TensorSpec((kvlr,), ("lora",), init="ones", dtype=cfg.dtype),
        "w_kr": TensorSpec((d, rope_d), ("d_model", "head_dim"), dtype=cfg.dtype),
        "w_uk": TensorSpec((kvlr, h, nope), ("lora", "heads", "head_dim"), dtype=cfg.dtype),
        "w_uv": TensorSpec((kvlr, h, vdim), ("lora", "heads", "head_dim"), dtype=cfg.dtype),
        "wo": TensorSpec((h, vdim, d), ("heads", "head_dim", "d_model"), dtype=cfg.dtype),
    }


def _mla_q(params: dict, x: jax.Array, positions: jax.Array, cfg):
    cq = rmsnorm(x @ params["w_dq"], params["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"])
    q_nope = q[..., : cfg.qk_nope_dim]
    q_rope = q[..., cfg.qk_nope_dim :]
    cos, sin = rope_for(positions, cfg.qk_rope_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _mla_ckv(params: dict, x: jax.Array, positions: jax.Array, cfg):
    c = rmsnorm(x @ params["w_dkv"], params["kv_norm"], cfg.norm_eps)
    kr = (x @ params["w_kr"])[:, :, None, :]  # (B, S, 1, rope)
    cos, sin = rope_for(positions, cfg.qk_rope_dim, cfg.rope_theta)
    kr = apply_rope(kr, cos, sin)[:, :, 0]  # (B, S, rope)
    return c, kr


def mla_prefill(
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    cfg,
    *,
    cache: dict | None = None,
) -> tuple[jax.Array, dict | None]:
    q_nope, q_rope = _mla_q(params, x, positions, cfg)
    c, kr = _mla_ckv(params, x, positions, cfg)
    # expand keys/values per head (standard form for prefill)
    k_nope = jnp.einsum("bsr,rhk->bshk", c, params["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", c, params["w_uv"])
    h = cfg.n_heads
    k_rope = jnp.broadcast_to(kr[:, :, None, :], (*kr.shape[:2], h, cfg.qk_rope_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    scale = 1.0 / np.sqrt(cfg.mla_qk_head_dim)
    # v_head_dim may differ from qk dim — pad v to qk dim for the shared
    # attention helper, then slice back.
    vdim = cfg.v_head_dim
    qk_dim = cfg.mla_qk_head_dim
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_dim - vdim))) if vdim < qk_dim else v
    if seq_sharded():
        # sequence-parallel attention (§Perf A2) — see gqa_prefill
        q = shard(q, "batch", "seq", None, None)
        k = shard(k, "batch", None, None, None)
        v_p = shard(v_p, "batch", None, None, None)
        out = chunked_attention(
            q, k, v_p, causal=True, softmax_scale=scale, q_chunk=q.shape[1]
        )
    else:
        out = chunked_attention(q, k, v_p, causal=True, softmax_scale=scale)
    out = out[..., :vdim]
    new_cache = None
    if cache is not None:
        lengths = positions[:, -1] + 1
        new_cache = {
            "ckv": jax.lax.dynamic_update_slice(
                cache["ckv"], c.astype(cache["ckv"].dtype), (0, 0, 0)
            ),
            "krope": jax.lax.dynamic_update_slice(
                cache["krope"], kr.astype(cache["krope"].dtype), (0, 0, 0)
            ),
            "lengths": lengths.astype(jnp.int32),
        }
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return shard(out, "batch", "seq", "act_d_model"), new_cache


def mla_decode(
    params: dict,
    x: jax.Array,  # (B, 1, d)
    cfg,
    cache: dict,
) -> tuple[jax.Array, dict]:
    b = x.shape[0]
    pos = cache["lengths"][:, None]  # (B, 1)
    q_nope, q_rope = _mla_q(params, x, pos, cfg)  # (B, 1, H, ·)
    c_new, kr_new = _mla_ckv(params, x, pos, cfg)
    new_cache = kvcache.append_mla(cache, c_new[:, 0], kr_new[:, 0])

    # absorbed decode: score = q_nope·(W_uk·c) + q_rope·kr
    #                        = (q_nope·W_uk)·c + q_rope·kr
    q_abs = jnp.einsum("bhk,rhk->bhr", q_nope[:, 0], params["w_uk"])  # (B, H, kvlr)
    scale = 1.0 / np.sqrt(cfg.mla_qk_head_dim)
    s_latent = jnp.einsum(
        "bhr,bsr->bhs", q_abs.astype(jnp.float32), new_cache["ckv"].astype(jnp.float32)
    )
    s_rope = jnp.einsum(
        "bhk,bsk->bhs", q_rope[:, 0].astype(jnp.float32), new_cache["krope"].astype(jnp.float32)
    )
    s = (s_latent + s_rope) * scale
    smax = new_cache["ckv"].shape[1]
    valid = jnp.arange(smax)[None, :] < new_cache["lengths"][:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # out = p · v = p · (W_uv·c): absorb on the output side too
    ctx = jnp.einsum("bhs,bsr->bhr", p, new_cache["ckv"].astype(jnp.float32))  # (B, H, kvlr)
    out = jnp.einsum("bhr,rhk->bhk", ctx, params["w_uv"].astype(jnp.float32))
    out = jnp.einsum("bhk,hkd->bd", out.astype(x.dtype), params["wo"])[:, None]
    return shard(out, "batch", "seq", "act_d_model"), new_cache
