"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import fused_rmsnorm

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,sk,h,kv,d",
    [
        (2, 64, 64, 4, 2, 32),   # GQA 2:1
        (1, 100, 100, 8, 8, 64),  # MHA, non-multiple seq
        (2, 128, 256, 4, 1, 16),  # MQA, cross lengths
        (1, 48, 32, 6, 3, 128),   # uneven blocks, mxu-width head
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(b, sq, sk, h, kv, d, causal, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (b, sq, h, d), dtype)
    k = jax.random.normal(k2, (b, sk, kv, d), dtype)
    v = jax.random.normal(k3, (b, sk, kv, d), dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
    want = ref.attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,h,kv,d",
    [(2, 128, 8, 2, 32), (3, 100, 4, 4, 64), (1, 256, 16, 8, 16), (2, 96, 8, 1, 128)],
)
def test_decode_attention_matches_oracle(b, s, h, kv, d, dtype):
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = jax.random.normal(k1, (b, h, d), dtype)
    kc = jax.random.normal(k2, (b, kv, s, d), dtype)
    vc = jax.random.normal(k3, (b, kv, s, d), dtype)
    lengths = jax.random.randint(k4, (b,), 1, s + 1)
    got = decode_attention(q, kc, vc, lengths, block_s=32, interpret=True)
    want = ref.decode_attention_reference(q, kc, vc, lengths)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


def test_decode_attention_respects_lengths():
    """Tokens beyond `lengths` must not affect the output."""
    b, s, h, kv, d = 2, 64, 4, 2, 16
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (b, h, d))
    kc = jax.random.normal(k2, (b, kv, s, d))
    vc = jax.random.normal(k3, (b, kv, s, d))
    lengths = jnp.array([10, 20])
    out1 = decode_attention(q, kc, vc, lengths, block_s=16, interpret=True)
    # scramble the invalid region
    mask = jnp.arange(s)[None, None, :, None] >= lengths[:, None, None, None]
    kc2 = jnp.where(mask, 99.0, kc)
    vc2 = jnp.where(mask, -99.0, vc)
    out2 = decode_attention(q, kc2, vc2, lengths, block_s=16, interpret=True)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 7, 64), (130, 256), (1, 32), (3, 5, 7, 16)])
def test_rmsnorm_matches_oracle(shape, dtype):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, shape, dtype)
    w = jax.random.normal(k2, (shape[-1],), dtype)
    got = fused_rmsnorm(x, w, block_n=16, interpret=True)
    want = ref.rmsnorm(x, w)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


def test_ops_dispatch_cpu_uses_ref():
    """Off the TPU the wrappers run the oracle only when asked (impl='ref'),
    the interpreter only when asked (interpret=True), and otherwise refuse
    instead of falling back."""
    from repro.kernels import ops

    b, s, h, kv, d = 1, 16, 4, 2, 8
    q = jax.random.normal(KEY, (b, s, h, d))
    k = jax.random.normal(KEY, (b, s, kv, d))
    v = jax.random.normal(KEY, (b, s, kv, d))
    want = ref.attention_reference(q, k, v, causal=True)
    out = ops.flash_attention(q, k, v, causal=True, impl="ref")
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    out = ops.flash_attention(q, k, v, causal=True, block_q=8, block_k=8, interpret=True)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    with pytest.raises(RuntimeError, match="interpret=True"):
        ops.flash_attention(q, k, v, causal=True)
    x = jax.random.normal(KEY, (3, 16))
    w = jnp.ones((16,))
    with pytest.raises(RuntimeError, match="interpret=True"):
        ops.rmsnorm(x, w)
    with pytest.raises(RuntimeError, match="interpret=True"):
        ops.decode_attention(q[:, 0], k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                             jnp.array([s]))
