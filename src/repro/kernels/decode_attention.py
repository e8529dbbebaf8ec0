"""Single-token GQA decode attention over a padded KV cache (flash-decoding
style) as a Pallas TPU kernel.

This is the serving hot loop: one query token per sequence against a long
cache.  It is *memory-bound* (every cache byte is read once per step), so the
kernel's job is to stream K/V HBM->VMEM at full bandwidth while the online
softmax rides along:

  * grid ``(batch, kv_heads, seq_blocks)`` — the seq axis is the
    accumulation axis (TPU sequential grid), carrying running
    (max, denom, acc) per *query-head group* in VMEM scratch;
  * GQA handled by blocking queries per KV head: the ``n_rep`` query heads
    that share one KV head are processed together as a (n_rep, D) tile, so
    each cache block is read once for all of them — the exact arithmetic-
    intensity trick GPU flash-decoding uses, expressed as a tile shape.
    Queries are viewed as ``(B, KV, n_rep, D)``, so the last two block
    dimensions are the whole ``(n_rep, D)`` group and the head axis is
    never tiled below the TPU's (8, 128) minimum;
  * per-sequence valid ``lengths`` arrive by scalar prefetch (SMEM): they
    mask the tail block, skip blocks past the end, and clamp the cache
    index map so those blocks are not fetched either.

VMEM per step: ``2 * block_s * D * 2B`` cache tile + ``n_rep x block_s``
f32 scores — ~0.3 MB at block_s=512, D=128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    len_ref,  # (B,) int32 in SMEM (scalar prefetch)
    q_ref,  # (1, 1, n_rep, D)
    k_ref,  # (1, 1, block_s, D)  — seq-major (B, KV, S, D) cache layout
    v_ref,  # (1, 1, block_s, D)
    o_ref,  # (1, 1, n_rep, D)
    m_ref,  # scratch (n_rep, 1) f32
    l_ref,  # scratch (n_rep, 1) f32
    acc_ref,  # scratch (n_rep, D) f32
    *,
    scale: float,
    block_s: int,
):
    bi = pl.program_id(0)
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[bi]

    @pl.when(si * block_s < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # (n_rep, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (n_rep, bs)
        pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # (B, H, D) — one new token per sequence
    k_cache: jax.Array,  # (B, KV, S, D)  — seq-major cache layout (§Perf C1)
    v_cache: jax.Array,  # (B, KV, S, D)
    lengths: jax.Array,  # (B,) valid cache entries
    *,
    softmax_scale: float | None = None,
    block_s: int = 512,
    interpret: bool,
) -> jax.Array:
    b, h, d = q.shape
    _, kv, s, _ = k_cache.shape
    n_rep = h // kv
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(d)

    block_s = min(block_s, max(s, 8))
    s_pad = -(-s // block_s) * block_s
    if s_pad != s:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))

    # (B, H, D) -> (B, KV, n_rep, D): query head h reads KV head h // n_rep
    qg = q.reshape(b, kv, n_rep, d)

    def kv_block(bi, hi, si, lens):
        # blocks past the sequence end re-use the last valid block index, so
        # the pipeline issues no copy for them (the kernel skips them too)
        last = jnp.maximum(lens[bi] - 1, 0) // block_s
        return (bi, hi, jnp.minimum(si, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv, s_pad // block_s),
        in_specs=[
            pl.BlockSpec((1, 1, n_rep, d), lambda bi, hi, si, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_s, d), kv_block),
            pl.BlockSpec((1, 1, block_s, d), kv_block),
        ],
        out_specs=pl.BlockSpec((1, 1, n_rep, d), lambda bi, hi, si, lens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_rep, 1), jnp.float32),
            pltpu.VMEM((n_rep, 1), jnp.float32),
            pltpu.VMEM((n_rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_s=block_s),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, n_rep, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, h, d)
