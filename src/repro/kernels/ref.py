"""Pure-jnp attention and RMSNorm: what ``repro.models`` runs, and what each
Pallas kernel in this package is tested against.

The model code imports these functions from here and runs them until a
Pallas kernel replaces one on its path.  ``ops.py``'s ``impl='ref'``
branches, ``tests/test_kernels.py`` (shape and dtype sweeps with
assert_allclose, the kernels in ``interpret=True`` mode on the CPU) and
``benchmarks/kernel_micro.py`` call them by name.  This module imports
nothing from ``repro``: it is the bottom of the import DAG.

The attention is *memory-bounded* (online-softmax over KV chunks, scanned
over Q chunks), so 32k-token prefills lower with O(block) live memory.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Attention math: memory-bounded online-softmax (the FA oracle)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, KV, D) -> (B, S, KV*n_rep, D) by repeating each kv head."""
    if n_rep == 1:
        return k
    b, s, kv, d = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, d))
    return k.reshape(b, s, kv * n_rep, d)


def attention_reference(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,  # (B, Sk, KV, D)
    *,
    causal: bool,
    q_offset: int | jax.Array = 0,
    kv_len: jax.Array | None = None,  # (B,) valid kv lengths
    softmax_scale: float | None = None,
) -> jax.Array:
    """Naive O(Sq*Sk) attention — the numerical oracle for kernels/tests."""
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(d)
    k = _repeat_kv(k, h // kv)
    v = _repeat_kv(v, h // kv)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(sk)
        mask = qpos[:, None] >= kpos[None, :]
    mask = jnp.broadcast_to(mask[None, None], (b, 1, sq, sk))
    if kv_len is not None:
        valid = jnp.arange(sk)[None, :] < kv_len[:, None]
        mask = mask & valid[:, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def chunked_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,  # (B, Sk, KV, D)
    *,
    causal: bool,
    q_offset: int | jax.Array = 0,
    kv_len: jax.Array | None = None,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
    softmax_scale: float | None = None,
) -> jax.Array:
    """Flash-attention-style exact attention with O(q_chunk*kv_chunk) live
    score memory: scan over Q chunks, inner scan over KV chunks carrying
    running (max, denominator, accumulator).

    This is what the 32k prefill lowers to on the production mesh; the Pallas
    kernel implements the same loop structure in VMEM.
    """
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    n_rep = h // kvh
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(d)

    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    # pad to multiples
    sq_pad = int(np.ceil(sq / q_chunk)) * q_chunk
    sk_pad = int(np.ceil(sk / kv_chunk)) * kv_chunk
    qp = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, sk_pad - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_pad - sk), (0, 0), (0, 0)))
    nq, nk = sq_pad // q_chunk, sk_pad // kv_chunk

    if kv_len is None:
        kv_len_arr = jnp.full((b,), sk, jnp.int32)
    else:
        kv_len_arr = kv_len.astype(jnp.int32)

    # (nq, B, C, H, D)
    qs = qp.reshape(b, nq, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    ks = kp.reshape(b, nk, kv_chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(b, nk, kv_chunk, kvh, d).transpose(1, 0, 2, 3, 4)

    def q_body(_, q_in):
        qi, qc = q_in  # chunk index, (B, Cq, H, D)
        q_pos = qi * q_chunk + jnp.arange(q_chunk) + q_offset  # (Cq,)

        def kv_body(carry, kv_in):
            m, l, acc = carry
            ki, kc, vc = kv_in
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)  # (Ck,)
            kr = _repeat_kv(kc, n_rep)  # (B, Ck, H, D)
            vr = _repeat_kv(vc, n_rep)
            s = (
                jnp.einsum("bqhd,bkhd->bhqk", qc.astype(jnp.float32), kr.astype(jnp.float32))
                * scale
            )
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]
            valid = k_pos[None, :] < kv_len_arr[:, None]  # (B, Ck)
            full_mask = mask[None, None] & valid[:, None, None, :]
            s = jnp.where(full_mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vr.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0), (jnp.arange(nk), ks, vs)
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 2, 1, 3)  # (B, Cq, H, D)

    _, outs = jax.lax.scan(q_body, None, (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, sq_pad, h, d)[:, :sq]
    return out.astype(q.dtype)


def decode_attention_reference(
    q: jax.Array,  # (B, H, D) — one new token per sequence
    k_cache: jax.Array,  # (B, KV, Smax, D)  — seq-major cache layout
    v_cache: jax.Array,  # (B, KV, Smax, D)
    lengths: jax.Array,  # (B,) number of valid cache entries (incl. new token)
    *,
    softmax_scale: float | None = None,
) -> jax.Array:
    """Single-token GQA decode against a (padded) KV cache.

    Grouped-query form: the ``n_rep`` query heads sharing a KV head contract
    against it directly — no materialized ``repeat_kv`` (which would read the
    cache ``n_rep`` x from HBM).  The (B, KV, S, D) cache layout matches the
    dot's batch dims, so no transpose copy of the cache is needed (§Perf C1);
    bf16 operands + f32 accumulation via preferred_element_type avoid a
    materialized f32 cache copy."""
    b, h, d = q.shape
    _, kvh, smax, _ = k_cache.shape
    rep = h // kvh
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(d)
    qg = q.reshape(b, kvh, rep, d)
    s = jnp.einsum(
        "bgrd,bgsd->bgrs", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale
    valid = jnp.arange(smax)[None, :] < lengths[:, None]  # (B, S)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bgrs,bgsd->bgrd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, h, d).astype(q.dtype)
