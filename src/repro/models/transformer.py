"""Unified multi-family model: dense / MoE / SSM / hybrid / enc-dec / VLM.

One parameter template + three execution paths (train / prefill / decode),
all expressed as ``lax.scan`` over *stacked* per-layer parameters so that

  * training remats layer-by-layer,
  * the autoscaling data plane can ship parameters as an ordered sequence of
    layer blocks (the unit of BlitzScale's multicast chains and live
    scaling), and
  * ``forward_layers_range`` executes an arbitrary ``[lo, hi)`` slice of
    layers — the *fine-grained layer-level serving abstraction* of the paper
    (§4): a partially-loaded instance runs layers ``[0, k)`` and forwards the
    activation to the overloaded instance for ``[k, L)``.

Layer families:
  dense / vlm : [norm1 -> GQA|MLA -> +res -> norm2 -> MLP -> +res]
  moe         : [norm1 -> GQA     -> +res -> norm2 -> MoE -> +res]
  ssm         : [norm1 -> Mamba2  -> +res]
  hybrid      : ssm layers with one *shared* (attn+MLP) block invoked every
                ``attn_every`` layers (zamba2)
  encdec      : encoder [non-causal GQA + MLP] x n_enc, decoder adds
                cross-attention against the encoder output (whisper)
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import (
    TensorSpec,
    constrain_layer_params,
    init_from_template,
    shard,
    stack_template,
)
from repro.kernels.ref import rmsnorm
from repro.models import attention, kvcache, layers, mamba2, moe
from repro.models.config import ModelConfig

# ---------------------------------------------------------------------------
# Templates
# ---------------------------------------------------------------------------


def _norm_spec(cfg, dim=None) -> TensorSpec:
    return TensorSpec(((dim or cfg.d_model),), ("d_model",), init="ones", dtype=cfg.dtype)


def attn_layer_template(cfg, *, cross: bool = False) -> dict:
    """One attention+mlp block (dense/moe/vlm/encdec families)."""
    t: dict[str, Any] = {"norm1": _norm_spec(cfg), "norm2": _norm_spec(cfg)}
    if cfg.attn == "mla":
        t["attn"] = attention.mla_template(cfg)
    else:
        t["attn"] = attention.gqa_template(cfg)
    if cross:
        t["norm_x"] = _norm_spec(cfg)
        t["xattn"] = attention.gqa_template(cfg)
    if cfg.n_experts:
        t["moe"] = moe.moe_template(cfg)
    else:
        t["mlp"] = layers.mlp_template(cfg)
    return t


def ssm_layer_template(cfg) -> dict:
    return {"norm1": _norm_spec(cfg), "mixer": mamba2.mamba2_template(cfg)}


def layer_template(cfg) -> dict:
    """The per-layer template of the *main* (decoder) stack."""
    if cfg.family == "ssm" or cfg.family == "hybrid":
        return ssm_layer_template(cfg)
    return attn_layer_template(cfg, cross=(cfg.family == "encdec"))


def param_template(cfg: ModelConfig) -> dict:
    """Full-model TensorSpec pytree.  ``layers`` leaves carry a leading
    stacked 'layers' axis (the scan/multicast-block axis)."""
    t: dict[str, Any] = {
        "embed": layers.embedding_template(cfg),
        "layers": stack_template(layer_template(cfg), cfg.n_layers),
        "final_norm": _norm_spec(cfg),
    }
    if cfg.family == "hybrid":
        # one *shared* attention+MLP block reused at every invocation site
        t["shared"] = attn_layer_template(cfg)
    if cfg.family == "encdec":
        t["encoder"] = stack_template(attn_layer_template(cfg), cfg.n_enc_layers)
        t["enc_norm"] = _norm_spec(cfg)
    return t


def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    return init_from_template(key, param_template(cfg))


def n_layer_blocks(cfg: ModelConfig) -> int:
    """Number of multicast/live-scaling blocks = scan layers (+enc for
    encdec, +1 shared block for hybrid)."""
    n = cfg.n_layers
    if cfg.family == "encdec":
        n += cfg.n_enc_layers
    if cfg.family == "hybrid":
        n += 1
    return n


# ---------------------------------------------------------------------------
# Single-layer forwards (train/prefill mode: full sequence)
# ---------------------------------------------------------------------------


def _residual_branch(cfg, branch: jax.Array) -> jax.Array:
    """An attention or MLP branch as it enters the residual add."""
    return branch * cfg.residual_scale if cfg.residual_scale != 1.0 else branch


def _attn_layer_fwd(
    cfg,
    lp: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
    cache: dict | None = None,
    enc_out: jax.Array | None = None,
    enc_lengths: jax.Array | None = None,
) -> tuple[jax.Array, dict | None, jax.Array]:
    """Full-sequence attention layer. Returns (x, new_cache, aux_loss)."""
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    if cfg.attn == "mla":
        a, new_cache = attention.mla_prefill(lp["attn"], h, positions, cfg, cache=cache)
    else:
        a, new_cache = attention.gqa_prefill(
            lp["attn"], h, positions, cfg, causal=causal, cache=cache
        )
    x = x + _residual_branch(cfg, a)
    if enc_out is not None:
        hx = rmsnorm(x, lp["norm_x"], cfg.norm_eps)
        # cross-attention: kv from encoder output, no rope, not causal
        kx = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wk"])
        vx = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wv"])
        ax, _ = attention.gqa_prefill(
            lp["xattn"], hx, positions, cfg, causal=False, use_rope=False,
            kv_override=(kx, vx),
        )
        x = x + ax
    h2 = rmsnorm(x, lp["norm2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.n_experts:
        m, aux = moe.moe_forward(lp["moe"], h2, cfg)
    else:
        m = layers.mlp_forward(lp["mlp"], h2, cfg)
    return x + _residual_branch(cfg, m), new_cache, aux


def _ssm_layer_fwd(
    cfg, lp: dict, x: jax.Array, *, state: dict | None = None
) -> tuple[jax.Array, dict | None]:
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    if state is None:
        out = mamba2.mamba2_forward(lp["mixer"], h, cfg)
        return x + out, None
    out, new_state = mamba2.mamba2_prefill(lp["mixer"], h, cfg, state)
    return x + out, new_state


# ---------------------------------------------------------------------------
# Single-layer forwards (decode mode: one token)
# ---------------------------------------------------------------------------


def _attn_layer_decode(
    cfg,
    lp: dict,
    x: jax.Array,  # (B, 1, d)
    cache: dict,
    cross_cache: dict | None = None,
) -> tuple[jax.Array, dict]:
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    if cfg.attn == "mla":
        a, new_cache = attention.mla_decode(lp["attn"], h, cfg, cache)
    else:
        a, new_cache = attention.gqa_decode(lp["attn"], h, cfg, cache)
    x = x + _residual_branch(cfg, a)
    if cross_cache is not None:
        hx = rmsnorm(x, lp["norm_x"], cfg.norm_eps)
        ax, _ = attention.gqa_decode(lp["xattn"], hx, cfg, cache, cross_cache=cross_cache)
        x = x + ax
    h2 = rmsnorm(x, lp["norm2"], cfg.norm_eps)
    if cfg.n_experts:
        m, _ = moe.moe_forward(lp["moe"], h2, cfg)
    else:
        m = layers.mlp_forward(lp["mlp"], h2, cfg)
    return x + _residual_branch(cfg, m), new_cache


def _ssm_layer_decode(cfg, lp: dict, x: jax.Array, state: dict) -> tuple[jax.Array, dict]:
    h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
    out, new_state = mamba2.mamba2_decode(lp["mixer"], h, cfg, state)
    return x + out, new_state


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def init_caches(cfg: ModelConfig, batch: int, max_seq: int, *, abstract: bool = False) -> dict:
    """Stacked per-layer decode caches for the whole model."""
    hd = cfg.resolved_head_dim

    def kv(b, s):
        if abstract:
            return kvcache.kv_cache_abstract(b, s, cfg.n_kv_heads, hd, cfg.dtype)
        return kvcache.init_kv_cache(b, s, cfg.n_kv_heads, hd, cfg.dtype)

    def mla(b, s):
        if abstract:
            return kvcache.mla_cache_abstract(b, s, cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.dtype)
        return kvcache.init_mla_cache(b, s, cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.dtype)

    def ssm(b):
        if abstract:
            return kvcache.ssm_state_abstract(b, cfg)
        return kvcache.init_ssm_state(b, cfg)

    def stack(tree_fn, n):
        """Add a leading layer axis to each cache leaf."""
        proto = tree_fn()
        if abstract:
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n, *s.shape), s.dtype), proto
            )
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n, *a.shape)).copy(), proto)

    caches: dict[str, Any] = {}
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        mk = (lambda: mla(batch, max_seq)) if cfg.attn == "mla" else (lambda: kv(batch, max_seq))
        caches["layers"] = stack(mk, cfg.n_layers)
    elif fam == "ssm":
        caches["layers"] = stack(lambda: ssm(batch), cfg.n_layers)
    elif fam == "hybrid":
        caches["layers"] = stack(lambda: ssm(batch), cfg.n_layers)
        n_sites = cfg.n_layers // cfg.attn_every
        caches["shared"] = stack(lambda: kv(batch, max_seq), n_sites)
    elif fam == "encdec":
        caches["layers"] = stack(lambda: kv(batch, max_seq), cfg.n_layers)
        # cross-attention K/V computed once at prefill from encoder output
        # (seq-major layout, matching the decode cache — §Perf C1)
        ek = (batch, cfg.n_kv_heads, cfg.n_frontend_tokens, hd)
        if abstract:
            caches["cross"] = {
                "k": jax.ShapeDtypeStruct((cfg.n_layers, *ek), cfg.dtype),
                "v": jax.ShapeDtypeStruct((cfg.n_layers, *ek), cfg.dtype),
                "lengths": jax.ShapeDtypeStruct((batch,), jnp.int32),
            }
        else:
            caches["cross"] = {
                "k": jnp.zeros((cfg.n_layers, *ek), cfg.dtype),
                "v": jnp.zeros((cfg.n_layers, *ek), cfg.dtype),
                "lengths": jnp.zeros((batch,), jnp.int32),
            }
    else:
        raise ValueError(fam)
    return caches


def cache_axes(cfg: ModelConfig) -> dict:
    """Logical-axis pytree matching ``init_caches`` output."""

    def add_layer(tree):
        return jax.tree.map(
            lambda axes: ("layers", *axes), tree, is_leaf=lambda x: isinstance(x, tuple)
        )

    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        base = (kvcache.mla_cache_axes() if cfg.attn == "mla"
                else kvcache.kv_cache_axes())
        return {"layers": add_layer(base)}
    if fam == "ssm":
        return {"layers": add_layer(kvcache.ssm_state_axes())}
    if fam == "hybrid":
        return {
            "layers": add_layer(kvcache.ssm_state_axes()),
            "shared": add_layer(kvcache.kv_cache_axes()),
        }
    if fam == "encdec":
        return {
            "layers": add_layer(kvcache.kv_cache_axes()),
            "cross": add_layer(kvcache.kv_cache_axes()),
        }
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# Embedding frontends
# ---------------------------------------------------------------------------


def _embed(cfg, params, tokens, frames=None):
    """tokens: (B, S) int32. frames: optional (B, Sf, d) stub modality
    embeddings — VLM patches overwrite the first Sf token positions."""
    x = layers.embed_tokens(params["embed"], tokens, cfg)
    if cfg.family == "vlm" and frames is not None:
        sf = frames.shape[1]
        mask = (jnp.arange(tokens.shape[1]) < sf)[None, :, None]
        fpad = jnp.pad(frames.astype(x.dtype), ((0, 0), (0, x.shape[1] - sf), (0, 0)))
        x = jnp.where(mask, fpad, x)
    return x


def _run_encoder(cfg, params, frames):
    """Whisper encoder over precomputed frame embeddings (stub frontend)."""
    x = frames
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1])[None], frames.shape[:2])

    def body(x, lp):
        x, _, _ = _attn_layer_fwd(cfg, lp, x, pos, causal=False)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return rmsnorm(x, params["enc_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Train forward (full sequence, no caches, remat over layers)
# ---------------------------------------------------------------------------


def train_forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S)
    frames: jax.Array | None = None,  # (B, Sf, d) for vlm/encdec stub frontends
) -> tuple[jax.Array, jax.Array]:
    """Returns (logits (B,S,V), moe_aux_loss)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = _embed(cfg, params, tokens, frames)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = _run_encoder(cfg, params, frames)

    fam = cfg.family

    if fam in ("dense", "vlm", "moe", "encdec"):

        def body(carry, lp):
            x, aux = carry
            x, _, a = _attn_layer_fwd(cfg, lp, x, positions, causal=True, enc_out=enc_out)
            return (x, aux + a), None

        body = jax.checkpoint(body) if cfg.remat else body
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    elif fam == "ssm":

        def body(x, lp):
            x, _ = _ssm_layer_fwd(cfg, lp, x)
            return x, None

        body = jax.checkpoint(body) if cfg.remat else body
        x, _ = jax.lax.scan(body, x, params["layers"])
        aux = jnp.zeros((), jnp.float32)
    elif fam == "hybrid":
        k = cfg.attn_every
        groups = cfg.n_layers // k
        grouped = jax.tree.map(lambda p: p.reshape(groups, k, *p.shape[1:]), params["layers"])

        def group_body(x, glp):
            def inner(x, lp):
                x, _ = _ssm_layer_fwd(cfg, lp, x)
                return x, None

            x, _ = jax.lax.scan(inner, x, glp)
            x, _, _ = _attn_layer_fwd(cfg, params["shared"], x, positions, causal=True)
            return x, None

        group_body = jax.checkpoint(group_body) if cfg.remat else group_body
        x, _ = jax.lax.scan(group_body, x, grouped)
        aux = jnp.zeros((), jnp.float32)
    else:
        raise ValueError(fam)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.unembed(params["embed"], x, cfg)
    return logits, aux


# ---------------------------------------------------------------------------
# Prefill (full prompt -> caches + last-position logits)
# ---------------------------------------------------------------------------


def prefill(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S)
    caches: dict,
    frames: jax.Array | None = None,
) -> tuple[jax.Array, dict]:
    """Returns (next-token ids (B,), filled caches)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = _embed(cfg, params, tokens, frames)
    fam = cfg.family
    new_caches = dict(caches)

    if fam in ("dense", "vlm", "moe"):

        def body(x, inp):
            lp, cache_l = inp
            x, new_c, _ = _attn_layer_fwd(cfg, lp, x, positions, causal=True, cache=cache_l)
            return x, new_c

        x, layer_caches = jax.lax.scan(body, x, (params["layers"], caches["layers"]))
        new_caches["layers"] = layer_caches
    elif fam == "ssm":

        def body(x, inp):
            lp, st = inp
            x, new_st = _ssm_layer_fwd(cfg, lp, x, state=st)
            return x, new_st

        x, layer_states = jax.lax.scan(body, x, (params["layers"], caches["layers"]))
        new_caches["layers"] = layer_states
    elif fam == "hybrid":
        k = cfg.attn_every
        groups = cfg.n_layers // k
        grouped = jax.tree.map(lambda p: p.reshape(groups, k, *p.shape[1:]), params["layers"])
        gstates = jax.tree.map(lambda c: c.reshape(groups, k, *c.shape[1:]), caches["layers"])

        def group_body(x, inp):
            glp, gst, shared_cache = inp

            def inner(x, i2):
                lp, st = i2
                x, new_st = _ssm_layer_fwd(cfg, lp, x, state=st)
                return x, new_st

            x, new_gst = jax.lax.scan(inner, x, (glp, gst))
            x, new_sc, _ = _attn_layer_fwd(
                cfg, params["shared"], x, positions, causal=True, cache=shared_cache
            )
            return x, (new_gst, new_sc)

        x, (new_states, new_shared) = jax.lax.scan(
            group_body, x, (grouped, gstates, caches["shared"])
        )
        new_caches["layers"] = jax.tree.map(
            lambda c: c.reshape(cfg.n_layers, *c.shape[2:]), new_states
        )
        new_caches["shared"] = new_shared
    elif fam == "encdec":
        enc_out = _run_encoder(cfg, params, frames)
        enc_len = jnp.full((b,), enc_out.shape[1], jnp.int32)

        def body(x, inp):
            lp, cache_l = inp
            # precompute this layer's cross K/V from encoder output
            kx = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wk"])
            vx = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wv"])
            x, new_c, _ = _attn_layer_fwd(
                cfg, lp, x, positions, causal=True, cache=cache_l,
                enc_out=enc_out, enc_lengths=enc_len,
            )
            # store cross K/V seq-major (B, KV, S, D) for transpose-free decode
            return x, (
                new_c,
                kx.transpose(0, 2, 1, 3).astype(cfg.dtype),
                vx.transpose(0, 2, 1, 3).astype(cfg.dtype),
            )

        x, (layer_caches, kxs, vxs) = jax.lax.scan(body, x, (params["layers"], caches["layers"]))
        new_caches["layers"] = layer_caches
        new_caches["cross"] = {"k": kxs, "v": vxs, "lengths": enc_len}
    else:
        raise ValueError(fam)

    x = rmsnorm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = layers.unembed(params["embed"], x, cfg)[:, 0]
    logits = layers.vocab_mask_logits(logits.astype(jnp.float32), cfg)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_caches


# ---------------------------------------------------------------------------
# Decode (one token per sequence against the caches)
# ---------------------------------------------------------------------------


def decode_step(
    cfg: ModelConfig,
    params: dict,
    last_tokens: jax.Array,  # (B,) int32
    caches: dict,
) -> tuple[jax.Array, dict]:
    """One auto-regressive step.  Returns (next-token ids (B,), caches)."""
    x = layers.embed_tokens(params["embed"], last_tokens[:, None], cfg)
    fam = cfg.family
    new_caches = dict(caches)

    if fam in ("dense", "vlm", "moe"):

        def body(x, inp):
            lp, cache_l = inp
            x, new_c = _attn_layer_decode(cfg, lp, x, cache_l)
            return x, new_c

        x, layer_caches = jax.lax.scan(body, x, (params["layers"], caches["layers"]))
        new_caches["layers"] = layer_caches
    elif fam == "ssm":

        def body(x, inp):
            lp, st = inp
            x, new_st = _ssm_layer_decode(cfg, lp, x, st)
            return x, new_st

        x, layer_states = jax.lax.scan(body, x, (params["layers"], caches["layers"]))
        new_caches["layers"] = layer_states
    elif fam == "hybrid":
        k = cfg.attn_every
        groups = cfg.n_layers // k
        grouped = jax.tree.map(lambda p: p.reshape(groups, k, *p.shape[1:]), params["layers"])
        gstates = jax.tree.map(lambda c: c.reshape(groups, k, *c.shape[1:]), caches["layers"])

        def group_body(x, inp):
            glp, gst, shared_cache = inp

            def inner(x, i2):
                lp, st = i2
                x, new_st = _ssm_layer_decode(cfg, lp, x, st)
                return x, new_st

            x, new_gst = jax.lax.scan(inner, x, (glp, gst))
            x, new_sc = _attn_layer_decode(cfg, params["shared"], x, shared_cache)
            return x, (new_gst, new_sc)

        x, (new_states, new_shared) = jax.lax.scan(
            group_body, x, (grouped, gstates, caches["shared"])
        )
        new_caches["layers"] = jax.tree.map(
            lambda c: c.reshape(cfg.n_layers, *c.shape[2:]), new_states
        )
        new_caches["shared"] = new_shared
    elif fam == "encdec":

        def body(x, inp):
            lp, cache_l, kx, vx = inp
            cross = {"k": kx, "v": vx, "lengths": caches["cross"]["lengths"]}
            x, new_c = _attn_layer_decode(cfg, lp, x, cache_l, cross_cache=cross)
            return x, new_c

        x, layer_caches = jax.lax.scan(
            body, x, (params["layers"], caches["layers"], caches["cross"]["k"], caches["cross"]["v"])
        )
        new_caches["layers"] = layer_caches
    else:
        raise ValueError(fam)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = layers.unembed(params["embed"], x, cfg)[:, 0]
    logits = layers.vocab_mask_logits(logits.astype(jnp.float32), cfg)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_caches


# ---------------------------------------------------------------------------
# Layer-range execution — BlitzScale's fine-grained serving abstraction
# ---------------------------------------------------------------------------


def forward_layers_range(
    cfg: ModelConfig,
    stacked_layers: dict,
    x: jax.Array,  # (B, S, d) activation entering layer `lo`
    lo: jax.Array | int,
    hi: jax.Array | int,
    positions: jax.Array,
    shared: dict | None = None,
) -> jax.Array:
    """Execute layers ``[lo, hi)`` of the main stack with dynamic bounds.

    This is the compute primitive behind live autoscaling: a scaling
    instance with ``k`` loaded layers runs ``forward_layers_range(0, k)``
    and ships the activation to the source instance which runs
    ``forward_layers_range(k, L)``.  Implemented as a masked scan so the
    bounds can be traced values (no per-k recompilation).
    """
    fam = cfg.family
    lo = jnp.asarray(lo, jnp.int32)
    hi = jnp.asarray(hi, jnp.int32)

    def body(x, inp):
        i, lp = inp
        active = (i >= lo) & (i < hi)
        if fam in ("ssm", "hybrid"):
            y, _ = _ssm_layer_fwd(cfg, lp, x)
        else:
            y, _, _ = _attn_layer_fwd(cfg, lp, x, positions, causal=True)
        x = jnp.where(active, y, x)
        if fam == "hybrid" and shared is not None:
            site = (i % cfg.attn_every) == (cfg.attn_every - 1)
            ys, _, _ = _attn_layer_fwd(cfg, shared, x, positions, causal=True)
            x = jnp.where(active & site, ys, x)
        return x, None

    idx = jnp.arange(cfg.n_layers)
    x, _ = jax.lax.scan(body, x, (idx, stacked_layers))
    return x


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S)
    labels: jax.Array,  # (B, S) — -100 = ignored
    frames: jax.Array | None = None,
) -> jax.Array:
    """Cross-entropy computed *vocab-sharded*: the (B, S, V) logits stay
    partitioned on the model axis; logsumexp reduces locally then all-reduces
    a (B, S) stat, and the gold logit is extracted with a fused iota-compare
    reduction instead of ``take_along_axis`` (a gather on a sharded dim would
    all-gather the full logits — 12.9 GiB/chip for granite train_4k)."""
    logits, aux = train_forward(cfg, params, tokens, frames)
    logits = shard(logits, "batch", "seq", "act_vocab")
    logits = layers.vocab_mask_logits(logits, cfg)
    valid = labels != -100
    safe = jnp.where(valid, labels, 0)

    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1)  # (B, S) — cross-shard max is a tiny all-reduce
    lse = jnp.log(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1)) + m
    vocab_idx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    gold = jnp.sum(jnp.where(vocab_idx == safe[..., None], lf, 0.0), axis=-1)
    nll = lse - gold
    loss = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
    return loss + aux
