"""Fused RMSNorm as a Pallas TPU kernel.

RMSNorm is invoked 2-4x per layer; unfused it costs three HBM round-trips
(read x for the square-mean, read x again for the scale, write out).  The
fused kernel streams each row tile HBM->VMEM once: square-reduce, rsqrt,
scale by the (VMEM-resident) weight vector, write — a pure bandwidth play,
~3x traffic reduction on the norm path.

Grid: ``(n_row_blocks,)`` over the flattened (tokens, d_model) view; the
weight vector rides in a ``(1, d)`` block pinned to block 0 of every step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (block_n, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def fused_rmsnorm(
    x: jax.Array,  # (..., d)
    w: jax.Array,  # (d,)
    eps: float = 1e-5,
    *,
    block_n: int = 256,
    interpret: bool,
) -> jax.Array:
    orig_shape = x.shape
    d = x.shape[-1]
    n = int(np.prod(orig_shape[:-1])) if len(orig_shape) > 1 else 1
    x2 = x.reshape(n, d)

    block_n = min(block_n, max(n, 1))
    n_pad = -(-n // block_n) * block_n
    if n_pad != n:
        x2 = jnp.pad(x2, ((0, n_pad - n), (0, 0)))

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), x.dtype),
        interpret=interpret,
    )(x2, w.reshape(1, d))
    return out[:n].reshape(orig_shape)
