"""Weights drawn from the seed by a counter-based hash.

Every element is a pure function of (seed, leaf name, layer, index), so the
program's whole parameter tree is made on the device in one jitted call, and
the reference makes any one layer, or any rows of the embedding, again from
the seed alone: it takes nothing that the program made.  Integer mixing is
exact on every backend, and the float steps are exact too (a 24-bit integer
to float32, and multiplications by powers of two) up to the one rounding to
the served dtype, so both sides get the same bits.

A leaf is described by ``Leaf``: its shape without the layer axis, its kind
and the axes it contracts over (its fan-in).  A ``matrix`` leaf is uniform
in [-a, a) with a the power of two nearest sqrt(3 / fan_in), so its standard
deviation is near 1/sqrt(fan_in); a ``norm`` leaf is uniform in [0.75, 1.25);
an ``embed`` leaf is uniform in [-1, 1).
"""

from __future__ import annotations

import dataclasses
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLD = np.uint32(0x9E3779B9)


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: tuple[int, ...]
    kind: str  # 'matrix' | 'norm' | 'embed'
    fan_in_axes: tuple[int, ...] = (0,)

    @property
    def amplitude(self) -> float:
        if self.kind == "embed":
            return 1.0
        fan_in = math.prod(self.shape[a] for a in self.fan_in_axes)
        return 2.0 ** round(math.log2(math.sqrt(3.0 / fan_in)))


def _fmix(h):
    h = h ^ (h >> 16)
    h = h * _M1
    h = h ^ (h >> 13)
    h = h * _M2
    return h ^ (h >> 16)


def seed_words(seed: int) -> np.ndarray:
    """Any whole number as two uint32 words (seeds may pass 2**32)."""
    s = int(seed) % (1 << 64)
    return np.array([s & 0xFFFFFFFF, s >> 32], np.uint32)


def _leaf_key(words: jax.Array, name: str, layer):
    """Key of one leaf in one layer; ``layer`` may be traced (an iota)."""
    k = _fmix(words[0] ^ np.uint32(zlib.crc32(name.encode())))
    k = _fmix(k ^ words[1])
    return _fmix(k ^ (jnp.asarray(layer).astype(jnp.uint32) * _GOLD + np.uint32(1)))


def _values(h, leaf: Leaf, dtype):
    u = (h >> 8).astype(jnp.float32) * np.float32(2.0**-24)  # [0, 1), exact
    if leaf.kind == "norm":
        return ((u - np.float32(0.5)) * np.float32(0.5) + np.float32(1.0)).astype(dtype)
    return ((u - np.float32(0.5)) * np.float32(2.0 * leaf.amplitude)).astype(dtype)


def _hash_axes(key, index_arrays):
    h = key
    for a, idx in enumerate(index_arrays):
        h = _fmix(h ^ (idx.astype(jnp.uint32) + np.uint32(0x632BE5AB * (a + 1) & 0xFFFFFFFF)))
    return h


def draw(words: jax.Array, name: str, leaf: Leaf, dtype, *, layer=-1, n_layers: int = 0):
    """The leaf's values.  With ``n_layers`` the result is stacked over a
    leading layer axis, layer ``l`` equal to ``draw(..., layer=l)``."""
    shape = leaf.shape
    if n_layers:
        full = (n_layers, *shape)
        key = _leaf_key(words, name, jax.lax.broadcasted_iota(jnp.int32, full, 0))
        idx = [jax.lax.broadcasted_iota(jnp.int32, full, a + 1) for a in range(len(shape))]
    else:
        key = _leaf_key(words, name, layer)
        idx = [jax.lax.broadcasted_iota(jnp.int32, shape, a) for a in range(len(shape))]
    return _values(_hash_axes(key, idx), leaf, dtype)


def draw_rows(words: jax.Array, name: str, leaf: Leaf, rows: jax.Array, dtype):
    """Rows ``rows`` of a 2-D leaf (an embedding gather), equal to those
    rows of ``draw``."""
    n = rows.shape[0]
    key = _leaf_key(words, name, -1)
    r = jnp.broadcast_to(rows[:, None], (n, leaf.shape[1]))
    c = jax.lax.broadcasted_iota(jnp.int32, (n, leaf.shape[1]), 1)
    return _values(_hash_axes(key, [r, c]), leaf, dtype)
