"""Jit'd public wrappers around the Pallas kernels.

``impl`` names the implementation: ``'pallas'`` runs the kernel, ``'ref'``
the pure-jnp oracle.  The wrappers never pick a backend or interpret mode
on their own: a Pallas call off the TPU is an error unless the caller asks
for the Pallas interpreter with ``interpret=True`` (tests, benchmarks).
The contract: every entry point is numerically interchangeable with its
``ref.py`` oracle (validated in tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.rmsnorm import fused_rmsnorm as _rmsnorm_pallas


def _check_compiled_backend(interpret: bool) -> None:
    backend = jax.default_backend()
    if not interpret and backend != "tpu":
        raise RuntimeError(
            f"Pallas TPU kernel requested on backend {backend!r}: pass "
            "interpret=True to run the Pallas interpreter, or impl='ref'"
        )


@functools.partial(
    jax.jit, static_argnames=("causal", "impl", "block_q", "block_k", "interpret")
)
def flash_attention(
    q, k, v, *, causal: bool = True, impl: str = "pallas", block_q: int = 128,
    block_k: int = 512, interpret: bool = False,
):
    """impl: 'pallas' | 'ref'."""
    if impl == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal)
    _check_compiled_backend(interpret)
    return _flash_pallas(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("impl", "block_s", "interpret"))
def decode_attention(
    q, k_cache, v_cache, lengths, *, impl: str = "pallas", block_s: int = 512,
    interpret: bool = False,
):
    if impl == "ref":
        return ref.decode_attention_ref(q, k_cache, v_cache, lengths)
    _check_compiled_backend(interpret)
    return _decode_pallas(
        q, k_cache, v_cache, lengths, block_s=block_s, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("eps", "impl", "block_n", "interpret"))
def rmsnorm(
    x, w, *, eps: float = 1e-5, impl: str = "pallas", block_n: int = 256,
    interpret: bool = False,
):
    if impl == "ref":
        return ref.rmsnorm_ref(x, w, eps)
    _check_compiled_backend(interpret)
    return _rmsnorm_pallas(x, w, eps, block_n=block_n, interpret=interpret)
