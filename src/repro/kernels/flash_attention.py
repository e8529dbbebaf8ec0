"""Blocked causal flash attention (GQA) as a Pallas TPU kernel.

TPU adaptation notes (vs. the CUDA flash-attention the serving papers use):

  * The kernel works on a head-major ``(B, H, S, D)`` view, so every block
    is ``(1, 1, block, D)``: the last two block dimensions are
    ``(block_q|block_k, head_dim)``, which the TPU compiler tiles as
    (sublanes, lanes).  A head axis among the last two would need blocks
    of 8 heads, so the wrapper transposes the public ``(B, S, H, D)``
    layout instead.
  * The grid is ``(batch, q_heads, q_blocks, kv_blocks)``; TPU executes the
    grid *sequentially* with the last dimension fastest, so the kv axis is an
    accumulation axis: running (max, denom, acc) live in VMEM scratch and the
    output block is emitted on the final kv step.  This replaces the CUDA
    pattern of a thread-block-local loop with warp shuffles.
  * Block shapes are MXU-aligned: ``block_q x head_dim`` and
    ``block_k x head_dim`` tiles with 128-multiples on the matmul dims, so
    the two einsums per step map onto 128x128 systolic passes.
  * GQA is folded into the BlockSpec index maps: query head ``h`` reads KV
    head ``h // (H/KV)`` — no materialized repeat_kv, no extra HBM traffic
    (the CUDA kernels do the same via pointer arithmetic).
  * VMEM working set per grid step:
    ``(block_q + 2*block_k) * head_dim * 2B + block_q*block_k*4B`` ---
    128/512 blocks with D=128 use ~0.6 MB, well under the ~16 MB/core VMEM
    budget, leaving room for XLA to double-buffer the HBM->VMEM streams.

Causality is enforced with an in-kernel mask on global positions; fully
masked kv blocks short-circuit via ``pl.when`` (no MXU work), which for long
sequences halves the executed steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF


def _flash_kernel(
    q_ref,  # (1, 1, block_q, D)
    k_ref,  # (1, 1, block_k, D)
    v_ref,  # (1, 1, block_k, D)
    o_ref,  # (1, 1, block_q, D)
    m_ref,  # scratch (block_q, 1) f32
    l_ref,  # scratch (block_q, 1) f32
    acc_ref,  # scratch (block_q, D) f32
    *,
    scale: float,
    block_q: int,
    block_k: int,
    seq_q: int,
    seq_k: int,
    causal: bool,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        mask = (k_pos < seq_k) & (q_pos < seq_q)
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal:
        # skip blocks strictly above the causal diagonal (no MXU work)
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_step)
    else:
        _step()

    @pl.when(ki == nk - 1)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,  # (B, Sk, KV, D)
    *,
    causal: bool = True,
    softmax_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 512,
    interpret: bool,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    n_rep = h // kv
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(d)

    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    sq_pad = -(-sq // block_q) * block_q
    sk_pad = -(-sk // block_k) * block_k
    # head-major views: (B, H, S, D) / (B, KV, S, D)
    q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    k = jnp.pad(k, ((0, 0), (0, sk_pad - sk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    v = jnp.pad(v, ((0, 0), (0, sk_pad - sk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)

    grid = (b, h, sq_pad // block_q, sk_pad // block_k)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            block_q=block_q,
            block_k=block_k,
            seq_q=sq,
            seq_k=sk,
            causal=causal,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, ki: (bi, hi // n_rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max
            pltpu.VMEM((block_q, 1), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq].transpose(0, 2, 1, 3)
