"""Decode-time state containers: GQA KV cache, MLA compressed cache, SSM state.

All caches are plain pytrees (dicts of arrays) so they flow through jit /
pjit / scan unchanged and can be sharded with PartitionSpecs.  ``lengths`` is
per-sequence so continuous batching can mix requests at different decode
depths in one batch: each append writes each row at that row's own length.

The serving engine holds one contiguous ``max_seq`` cache per slot.
``PagedKVCache`` (block tables, host numpy) is not used by the engine; only
tests use it, and a device-side paged cache is future work.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Contiguous GQA KV cache
# ---------------------------------------------------------------------------


def init_kv_cache(batch: int, max_seq: int, n_kv: int, head_dim: int, dtype) -> dict:
    """Cache layout is (B, KV, S, D) — seq-major per KV head.  The decode
    dot contracts D with batch dims (B, KV), so this layout feeds the MXU
    directly; the (B, S, KV, D) activation layout would force a physical
    transpose copy of the whole cache every layer (§Perf C1: ~12 ms/step for
    granite decode_32k)."""
    return {
        "k": jnp.zeros((batch, n_kv, max_seq, head_dim), dtype),
        "v": jnp.zeros((batch, n_kv, max_seq, head_dim), dtype),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def kv_cache_abstract(batch: int, max_seq: int, n_kv: int, head_dim: int, dtype) -> dict:
    return {
        "k": jax.ShapeDtypeStruct((batch, n_kv, max_seq, head_dim), dtype),
        "v": jax.ShapeDtypeStruct((batch, n_kv, max_seq, head_dim), dtype),
        "lengths": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def kv_cache_axes() -> dict:
    """Logical axes for sharding the cache."""
    return {
        "k": ("cache_batch", "cache_kv_heads", "cache_seq", "head_dim"),
        "v": ("cache_batch", "cache_kv_heads", "cache_seq", "head_dim"),
        "lengths": ("cache_batch",),
    }


def write_prompt_kv(cache: dict, k: jax.Array, v: jax.Array, lengths: jax.Array) -> dict:
    """Write a full prompt's K/V (B, S, KV, D activations) at positions
    [0, S) — one transpose at prefill time (amortized over all decodes)."""
    kt = k.transpose(0, 2, 1, 3)  # (B, KV, S, D)
    vt = v.transpose(0, 2, 1, 3)
    out = {"lengths": lengths.astype(jnp.int32)}
    out["k"] = jax.lax.dynamic_update_slice(cache["k"], kt.astype(cache["k"].dtype), (0, 0, 0, 0))
    out["v"] = jax.lax.dynamic_update_slice(cache["v"], vt.astype(cache["v"].dtype), (0, 0, 0, 0))
    return out


def append_kv(cache: dict, k_new: jax.Array, v_new: jax.Array) -> dict:
    """Append one token's K/V (B, KV, D) at each sequence's current length.

    Implemented as a masked ``where`` over the seq axis rather than a
    per-batch scatter: a scatter with runtime indices onto the seq-SHARDED
    cache dim makes GSPMD all-gather the whole cache (measured 4.8 GiB/chip
    per decode step for granite decode_32k); the iota-compare form is
    elementwise, fully partitionable, and fuses into the attention read."""
    idx = cache["lengths"]  # (B,)
    smax = cache["k"].shape[2]
    mask = jnp.arange(smax)[None, None, :, None] == idx[:, None, None, None]

    def write(buf, new):  # new: (B, KV, D) -> broadcast over the seq axis
        return jnp.where(mask, new[:, :, None, :].astype(buf.dtype), buf)

    out = {"lengths": idx + 1}
    out["k"] = write(cache["k"], k_new)
    out["v"] = write(cache["v"], v_new)
    return out


# ---------------------------------------------------------------------------
# MLA compressed cache (latent c_kv + shared rope key per token)
# ---------------------------------------------------------------------------


def init_mla_cache(batch: int, max_seq: int, kv_lora_rank: int, rope_dim: int, dtype) -> dict:
    return {
        "ckv": jnp.zeros((batch, max_seq, kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_seq, rope_dim), dtype),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def mla_cache_abstract(batch: int, max_seq: int, kv_lora_rank: int, rope_dim: int, dtype) -> dict:
    return {
        "ckv": jax.ShapeDtypeStruct((batch, max_seq, kv_lora_rank), dtype),
        "krope": jax.ShapeDtypeStruct((batch, max_seq, rope_dim), dtype),
        "lengths": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def mla_cache_axes() -> dict:
    return {
        "ckv": ("cache_batch", "cache_seq", None),
        "krope": ("cache_batch", "cache_seq", None),
        "lengths": ("cache_batch",),
    }


def append_mla(cache: dict, ckv_new: jax.Array, krope_new: jax.Array) -> dict:
    """Masked-where append (see ``append_kv`` for why not a scatter)."""
    idx = cache["lengths"]
    smax = cache["ckv"].shape[1]
    mask = jnp.arange(smax)[None, :] == idx[:, None]  # (B, S)

    def write(buf, new):
        return jnp.where(mask[..., None], new[:, None].astype(buf.dtype), buf)

    return {
        "ckv": write(cache["ckv"], ckv_new),
        "krope": write(cache["krope"], krope_new),
        "lengths": idx + 1,
    }


# ---------------------------------------------------------------------------
# Mamba2 SSM state (constant-size: this is why long_500k is SSM-only)
# ---------------------------------------------------------------------------


def init_ssm_state(batch: int, cfg) -> dict:
    d_xbc = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, d_xbc), cfg.dtype),
        "h": jnp.zeros(
            (batch, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state), jnp.float32
        ),
    }


def ssm_state_abstract(batch: int, cfg) -> dict:
    d_xbc = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
    return {
        "conv": jax.ShapeDtypeStruct((batch, cfg.ssm_conv - 1, d_xbc), cfg.dtype),
        "h": jax.ShapeDtypeStruct(
            (batch, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state), jnp.float32
        ),
    }


def ssm_state_axes() -> dict:
    return {
        "conv": ("cache_batch", None, None),
        "h": ("cache_batch", "ssm_heads", None, None),
    }


# ---------------------------------------------------------------------------
# Paged KV cache (vLLM-style block tables; not used by the serving engine)
# ---------------------------------------------------------------------------


class PagedKVCache:
    """Host-managed paged cache: a pool of fixed-size blocks plus per-request
    block tables, gathered into contiguous form on request.  Numerics are
    identical to the contiguous cache (see tests).  ``InstanceEngine`` does
    not use it: its slots hold contiguous ``max_seq`` caches.  A paged cache
    on the device, with prefix reuse, is future work.
    """

    def __init__(self, n_blocks: int, block_size: int, n_kv: int, head_dim: int, dtype):
        self.block_size = block_size
        self.n_kv = n_kv
        self.head_dim = head_dim
        self.k_pool = np.zeros((n_blocks, block_size, n_kv, head_dim), dtype=np.float32)
        self.v_pool = np.zeros((n_blocks, block_size, n_kv, head_dim), dtype=np.float32)
        self.free: list[int] = list(range(n_blocks))[::-1]
        self.tables: dict[int, list[int]] = {}
        self.lengths: dict[int, int] = {}
        self._dtype = dtype

    @property
    def n_free_blocks(self) -> int:
        return len(self.free)

    def allocate(self, req_id: int) -> None:
        assert req_id not in self.tables
        self.tables[req_id] = []
        self.lengths[req_id] = 0

    def release(self, req_id: int) -> None:
        self.free.extend(self.tables.pop(req_id, []))
        self.lengths.pop(req_id, None)

    def _ensure_capacity(self, req_id: int, new_len: int) -> None:
        need = -(-new_len // self.block_size)  # ceil
        table = self.tables[req_id]
        while len(table) < need:
            if not self.free:
                raise MemoryError("paged KV cache exhausted")
            table.append(self.free.pop())

    def append(self, req_id: int, k: np.ndarray, v: np.ndarray) -> None:
        """k/v: (T, KV, D) — append T tokens for request req_id."""
        t = k.shape[0]
        start = self.lengths[req_id]
        self._ensure_capacity(req_id, start + t)
        table = self.tables[req_id]
        for i in range(t):
            pos = start + i
            blk, off = table[pos // self.block_size], pos % self.block_size
            self.k_pool[blk, off] = k[i]
            self.v_pool[blk, off] = v[i]
        self.lengths[req_id] = start + t

    def gather(self, req_id: int, max_seq: int) -> tuple[np.ndarray, np.ndarray, int]:
        """Materialize a contiguous (max_seq, KV, D) view for the jit step."""
        length = self.lengths[req_id]
        k = np.zeros((max_seq, self.n_kv, self.head_dim), np.float32)
        v = np.zeros_like(k)
        table = self.tables[req_id]
        for pos in range(length):
            blk, off = table[pos // self.block_size], pos % self.block_size
            k[pos] = self.k_pool[blk, off]
            v[pos] = self.v_pool[blk, off]
        return k, v, length
