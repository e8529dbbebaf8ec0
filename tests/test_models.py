"""Per-arch smoke tests (reduced configs) + model numerics properties."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, PAPER_IDS, get_config
from repro.models import transformer as TF
from repro.models import mamba2
from repro.models.config import ModelConfig

KEY = jax.random.PRNGKey(0)


def _frames_for(cfg, b):
    if cfg.family in ("vlm", "encdec"):
        n = max(cfg.n_frontend_tokens, 4)
        return jax.random.normal(KEY, (b, n, cfg.d_model), cfg.dtype) * 0.02
    return None


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCH_IDS + PAPER_IDS)
def test_arch_smoke_forward_prefill_decode(arch):
    """One forward + train loss + prefill + decode step per architecture:
    shapes correct, outputs finite."""
    cfg = get_config(arch, reduced=True)
    params = TF.init_params(KEY, cfg)
    b, s = 2, 24
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    frames = _frames_for(cfg, b)

    logits, aux = TF.train_forward(cfg, params, tokens, frames)
    assert logits.shape == (b, s, cfg.padded_vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())

    loss = TF.lm_loss(cfg, params, tokens, tokens, frames)
    assert bool(jnp.isfinite(loss)) and float(loss) > 0

    caches = TF.init_caches(cfg, b, s + 8)
    nxt, caches = TF.prefill(cfg, params, tokens, caches, frames)
    assert nxt.shape == (b,) and int(nxt.max()) < cfg.vocab_size
    nxt2, caches = TF.decode_step(cfg, params, nxt, caches)
    assert nxt2.shape == (b,) and int(nxt2.max()) < cfg.vocab_size


@pytest.mark.parametrize("arch", ["granite-8b", "minicpm3-4b", "mamba2-370m", "zamba2-2.7b"])
def test_prefill_then_decode_matches_longer_prefill(arch):
    """KV-cache correctness: prefill(S)+decode(1) must predict the same
    next-token as prefill(S+1) given teacher-forced input."""
    cfg = get_config(arch, reduced=True)
    params = TF.init_params(jax.random.PRNGKey(1), cfg)
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(2), (b, s + 1), 0, cfg.vocab_size)

    caches = TF.init_caches(cfg, b, s + 8)
    _, caches = TF.prefill(cfg, params, tokens[:, :s], caches)
    nxt_inc, _ = TF.decode_step(cfg, params, tokens[:, s], caches)

    caches2 = TF.init_caches(cfg, b, s + 8)
    nxt_full, _ = TF.prefill(cfg, params, tokens, caches2)
    np.testing.assert_array_equal(np.asarray(nxt_inc), np.asarray(nxt_full))


def test_gqa_equals_mha_when_kv_equals_heads():
    """GQA with kv=h is plain MHA: grouped attention must equal reference."""
    from repro.models.layers import attention_reference, chunked_attention

    b, s, h, d = 2, 33, 4, 16
    q = jax.random.normal(KEY, (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(3), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(4), (b, s, h, d))
    out_c = chunked_attention(q, k, v, causal=True, q_chunk=8, kv_chunk=16)
    out_r = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out_c, out_r, atol=2e-5, rtol=2e-5)


def test_mamba2_chunked_equals_sequential():
    """SSD chunked algorithm == naive per-token recurrence."""
    b, s, h, p, g, n = 2, 64, 4, 8, 2, 16
    k1, k2, k3, k4, k5 = jax.random.split(KEY, 5)
    x = jax.random.normal(k1, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k2, (b, s, h)))
    a = -jnp.exp(jax.random.uniform(k3, (h,)))
    b_in = jax.random.normal(k4, (b, s, g, n))
    c_in = jax.random.normal(k5, (b, s, g, n))
    y_chunk, h_chunk = mamba2.ssd_chunked(x, dt, a, b_in, c_in, chunk=16)
    y_seq, h_seq = mamba2.ssd_reference(x, dt, a, b_in, c_in)
    np.testing.assert_allclose(y_chunk, y_seq, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h_chunk, h_seq, atol=1e-4, rtol=1e-4)


def test_mamba2_decode_continues_prefill():
    """Recurrent decode from the prefill state == prefill over the longer
    sequence (state-space consistency)."""
    cfg = get_config("mamba2-370m", reduced=True)
    params = TF.init_params(jax.random.PRNGKey(5), cfg)
    b, s = 2, 24
    tokens = jax.random.randint(jax.random.PRNGKey(6), (b, s + 1), 0, cfg.vocab_size)
    caches = TF.init_caches(cfg, b, s + 8)
    _, caches = TF.prefill(cfg, params, tokens[:, :s], caches)
    nxt_inc, _ = TF.decode_step(cfg, params, tokens[:, s], caches)
    caches2 = TF.init_caches(cfg, b, s + 8)
    nxt_full, _ = TF.prefill(cfg, params, tokens, caches2)
    np.testing.assert_array_equal(np.asarray(nxt_inc), np.asarray(nxt_full))


def test_moe_router_prob_mass_and_aux_loss():
    from repro.models import moe

    cfg = get_config("olmoe-1b-7b", reduced=True)
    params_tree = TF.init_params(KEY, cfg)
    lp = jax.tree.map(lambda x: x[0], params_tree["layers"])  # layer 0
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), cfg.dtype)
    out, aux = moe.moe_forward(lp["moe"], x, cfg)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    # balanced-ish routing: aux loss near coef (perfect balance -> coef * 1)
    assert 0 < float(aux) < cfg.router_aux_coef * cfg.n_experts


def test_vocab_padding_masked():
    """Padded vocab rows must never be predicted."""
    cfg = get_config("minicpm3-4b", reduced=True).replace(vocab_size=250)  # pads to 512
    assert cfg.padded_vocab_size == 512
    params = TF.init_params(KEY, cfg)
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size)
    caches = TF.init_caches(cfg, 2, 16)
    nxt, caches = TF.prefill(cfg, params, tokens, caches)
    for _ in range(3):
        nxt, caches = TF.decode_step(cfg, params, nxt, caches)
        assert int(nxt.max()) < 250


@pytest.mark.slow
def test_forward_layers_range_composes():
    """forward_layers_range(0,k) ∘ forward_layers_range(k,L) == full stack —
    the layer-level serving abstraction is exact (paper §4)."""
    cfg = get_config("granite-8b", reduced=True)
    params = TF.init_params(KEY, cfg)
    b, s = 2, 12
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = TF._embed(cfg, params, tokens)
    full = TF.forward_layers_range(cfg, params["layers"], x, 0, cfg.n_layers, positions)
    for k in [0, 1, cfg.n_layers // 2, cfg.n_layers]:
        a = TF.forward_layers_range(cfg, params["layers"], x, 0, k, positions)
        out = TF.forward_layers_range(cfg, params["layers"], a, k, cfg.n_layers, positions)
        np.testing.assert_allclose(
            out.astype(jnp.float32), full.astype(jnp.float32), atol=1e-2, rtol=1e-2
        )


@pytest.mark.parametrize("arch", ["granite-8b", "mamba2-370m"])
def test_jitted_param_init_equals_eager_init(arch):
    """Per-leaf jitted init (no float32 temporaries on the device) draws the
    same values as the same init run eagerly op by op.  XLA fuses the
    normal draw's last multiply with the init scale, which moves some float32
    values by one ulp; after the cast to bf16 that shows at a rounding
    boundary only, as at most one bf16 ulp in a handful of elements (float32
    leaves such as Mamba2's A init likewise move by at most one ulp)."""
    cfg = get_config(arch, reduced=True)
    jitted = TF.init_params(KEY, cfg)
    with jax.disable_jit():
        eager = TF.init_params(KEY, cfg)
    n_diff = n_total = 0
    for a, b in zip(jax.tree.leaves(jitted), jax.tree.leaves(eager)):
        assert a.dtype == b.dtype and a.shape == b.shape
        uint = {2: np.uint16, 4: np.uint32}[a.dtype.itemsize]
        bits_a = np.asarray(a).view(uint).astype(np.int64)
        bits_b = np.asarray(b).view(uint).astype(np.int64)
        assert np.abs(bits_a - bits_b).max() <= 1  # one ulp, same sign
        n_diff += int((bits_a != bits_b).sum())
        n_total += bits_a.size
    assert n_diff <= n_total * 1e-3


def test_granite_one_chip_config_keeps_published_widths():
    """The one-chip variant cuts depth only: one stage of two."""
    full = get_config("granite-8b")
    one = get_config("granite-8b", one_chip=True)
    assert one.n_layers == full.n_layers // 2 == 18
    assert dataclasses.replace(one, n_layers=full.n_layers) == full
    with pytest.raises(ValueError, match="one-chip"):
        get_config("qwen1.5-4b", one_chip=True)
    with pytest.raises(ValueError):
        get_config("granite-8b", reduced=True, one_chip=True)
