"""Mamba2 (SSD — state-space duality) mixer block.  [arXiv:2405.21060]

Chunked SSD algorithm:
  * within-chunk: quadratic attention-like form  Y_diag = (C B^T ∘ L) X
  * chunk boundary states:  S_c = Σ_j decay_j · dt_j · B_j ⊗ X_j
  * inter-chunk: linear recurrence  h_c = γ_c h_{c-1} + S_c  (lax.scan)
  * off-diagonal contribution: Y_off = C · h_{c-1} · decay_in

Decode is the O(1) recurrent form over the (H, P, N) state — this is why the
long_500k cell is SSM/hybrid-only.  A sequential-scan reference
(``ssd_reference``) backs the property test chunked == sequential.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import TensorSpec, shard
from repro.kernels.ref import rmsnorm


def mamba2_template(cfg) -> dict[str, TensorSpec]:
    d = cfg.d_model
    din = cfg.d_inner
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    h = cfg.ssm_nheads
    d_xbc = din + 2 * g * n
    d_in_proj = 2 * din + 2 * g * n + h  # z, x, B, C, dt
    return {
        "in_proj": TensorSpec((d, d_in_proj), ("d_model", "d_ff"), dtype=cfg.dtype),
        "conv_w": TensorSpec((cfg.ssm_conv, d_xbc), ("conv", "d_ff"), dtype=cfg.dtype),
        "conv_b": TensorSpec((d_xbc,), ("d_ff",), init="zeros", dtype=cfg.dtype),
        "a_log": TensorSpec((h,), ("ssm_heads",), init="ssm_a", dtype=jnp.float32),
        "d_skip": TensorSpec((h,), ("ssm_heads",), init="ones", dtype=jnp.float32),
        "dt_bias": TensorSpec((h,), ("ssm_heads",), init="ssm_dt", dtype=jnp.float32),
        "norm_w": TensorSpec((din,), ("d_ff",), init="ones", dtype=cfg.dtype),
        "out_proj": TensorSpec((din, d), ("d_ff", "d_model"), dtype=cfg.dtype),
    }


def _split_in_proj(cfg, zxbcdt: jax.Array):
    din = cfg.d_inner
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    h = cfg.ssm_nheads
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din : din + din + 2 * g * n]
    dt = zxbcdt[..., -h:]
    return z, xbc, dt


def _split_xbc(cfg, xbc: jax.Array):
    din = cfg.d_inner
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    x = xbc[..., :din]
    b = xbc[..., din : din + g * n]
    c = xbc[..., din + g * n :]
    return x, b, c


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d. xbc: (B, S, D), w: (K, D)."""
    k = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + xbc.shape[1], :] * w[i] for i in range(k))
    return jax.nn.silu((out + b).astype(jnp.float32)).astype(xbc.dtype)


def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[i, j] = sum_{j < t <= i} x[t]; -inf for j > i."""
    t = x.shape[-1]
    cum = jnp.cumsum(x, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((t, t), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)  — already softplus'd
    a: jax.Array,  # (H,) negative
    b_in: jax.Array,  # (B, S, G, N)
    c_in: jax.Array,  # (B, S, G, N)
    chunk: int,
    h_init: jax.Array | None = None,  # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), h_final (B,H,P,N))."""
    bsz, s, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    rep = h // g
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bf = jnp.repeat(b_in.astype(jnp.float32), rep, axis=2)  # (B,S,H,N)
    cf = jnp.repeat(c_in.astype(jnp.float32), rep, axis=2)

    # chunked views: (B, nc, Q, ...)
    xc = xf.reshape(bsz, nc, chunk, h, p)
    dtc = dtf.reshape(bsz, nc, chunk, h)
    bc = bf.reshape(bsz, nc, chunk, h, n)
    cc = cf.reshape(bsz, nc, chunk, h, n)

    da = dtc * a[None, None, None, :]  # (B, nc, Q, H)
    da_t = da.transpose(0, 1, 3, 2)  # (B, nc, H, Q)
    cum = jnp.cumsum(da_t, axis=-1)  # (B, nc, H, Q)

    # (1) within-chunk (quadratic) term
    l_mat = jnp.exp(_segsum(da_t))  # (B, nc, H, Q, Q)
    scores = jnp.einsum("bcqhn,bckhn->bchqk", cc, bc)
    scores = scores * l_mat * dtc.transpose(0, 1, 3, 2)[:, :, :, None, :]  # dt_j on key side
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", scores, xc)

    # (2) per-chunk boundary states: S_c = Σ_j exp(cum_last - cum_j) dt_j B_j ⊗ X_j
    decay_to_end = jnp.exp(cum[..., -1:] - cum)  # (B, nc, H, Q)
    sc = jnp.einsum(
        "bchq,bcqh,bcqhn,bcqhp->bchpn", decay_to_end, dtc, bc, xc
    )  # (B, nc, H, P, N)

    # (3) inter-chunk recurrence
    gamma = jnp.exp(cum[..., -1])  # (B, nc, H) total decay per chunk

    def rec(carry, inp):
        s_c, gam = inp  # (B,H,P,N), (B,H)
        h_prev = carry
        h_new = h_prev * gam[..., None, None] + s_c
        return h_new, h_prev  # emit state *entering* the chunk

    h0 = (
        h_init.astype(jnp.float32)
        if h_init is not None
        else jnp.zeros((bsz, h, p, n), jnp.float32)
    )
    sc_t = sc.transpose(1, 0, 2, 3, 4)  # (nc, B, H, P, N)
    gam_t = gamma.transpose(1, 0, 2)  # (nc, B, H)
    h_final, h_enter = jax.lax.scan(rec, h0, (sc_t, gam_t))
    h_enter = h_enter.transpose(1, 0, 2, 3, 4)  # (B, nc, H, P, N)

    # (4) off-diagonal: Y_off = decay_in · C · h_enter
    decay_in = jnp.exp(cum)  # (B, nc, H, Q) decay from chunk start to q
    y_off = jnp.einsum("bcqhn,bchpn,bchq->bcqhp", cc, h_enter, decay_in)

    y = (y_diag + y_off).reshape(bsz, s, h, p)
    return y.astype(x.dtype), h_final


def ssd_reference(x, dt, a, b_in, c_in, h_init=None):
    """Sequential per-token recurrence — oracle for the chunked path."""
    bsz, s, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    rep = h // g
    bf = jnp.repeat(b_in.astype(jnp.float32), rep, axis=2)
    cf = jnp.repeat(c_in.astype(jnp.float32), rep, axis=2)
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)

    def step(h_prev, t):
        xt, dtt, bt, ct = t
        decay = jnp.exp(dtt * a)[..., None, None]  # (B,H,1,1)
        h_new = h_prev * decay + dtt[..., None, None] * (
            xt[..., :, None] * bt[:, :, None, :]
        )
        y = jnp.einsum("bhn,bhpn->bhp", ct, h_new)
        return h_new, y

    h0 = (
        h_init.astype(jnp.float32)
        if h_init is not None
        else jnp.zeros((bsz, h, p, n), jnp.float32)
    )
    xs = (
        xf.transpose(1, 0, 2, 3),
        dtf.transpose(1, 0, 2),
        bf.transpose(1, 0, 2, 3),
        cf.transpose(1, 0, 2, 3),
    )
    h_final, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), h_final


def mamba2_forward(
    params: dict,
    u: jax.Array,  # (B, S, d_model)
    cfg,
) -> jax.Array:
    """Full-sequence forward (train/prefill)."""
    b, s, _ = u.shape
    zxbcdt = u @ params["in_proj"]
    z, xbc, dt = _split_in_proj(cfg, zxbcdt)
    xbc = _causal_conv(xbc, params["conv_w"], params["conv_b"])
    x, b_in, c_in = _split_xbc(cfg, xbc)
    h = cfg.ssm_nheads
    x = x.reshape(b, s, h, cfg.ssm_headdim)
    b_in = b_in.reshape(b, s, cfg.ssm_ngroups, cfg.ssm_state)
    c_in = c_in.reshape(b, s, cfg.ssm_ngroups, cfg.ssm_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])

    # pad sequence to chunk multiple
    chunk = cfg.ssm_chunk
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y, _ = ssd_chunked(x, dt, params["a_log"], b_in, c_in, chunk)
    y = y[:, :s]
    y = y + params["d_skip"][None, None, :, None] * x[:, :s].astype(jnp.float32)
    y = y.reshape(b, s, cfg.d_inner).astype(u.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype), params["norm_w"], cfg.norm_eps)
    out = y @ params["out_proj"]
    return shard(out, "batch", "seq", "act_d_model")


def mamba2_prefill(params: dict, u: jax.Array, cfg, state: dict) -> tuple[jax.Array, dict]:
    """Prefill that also produces the decode state (conv tail + final h)."""
    b, s, _ = u.shape
    zxbcdt = u @ params["in_proj"]
    z, xbc_raw, dt = _split_in_proj(cfg, zxbcdt)
    xbc = _causal_conv(xbc_raw, params["conv_w"], params["conv_b"])
    x, b_in, c_in = _split_xbc(cfg, xbc)
    h = cfg.ssm_nheads
    x = x.reshape(b, s, h, cfg.ssm_headdim)
    b_in = b_in.reshape(b, s, cfg.ssm_ngroups, cfg.ssm_state)
    c_in = c_in.reshape(b, s, cfg.ssm_ngroups, cfg.ssm_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    chunk = cfg.ssm_chunk
    pad = (-s) % chunk
    if pad:
        xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dtp = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bp = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cp = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        xp, dtp, bp, cp = x, dt, b_in, c_in
    y, h_final = ssd_chunked(xp, dtp, params["a_log"], bp, cp, chunk)
    # NOTE: padded steps have dt=0 -> decay=1, no state update; h_final exact.
    y = y[:, :s]
    y = y + params["d_skip"][None, None, :, None] * x.astype(jnp.float32)
    y = y.reshape(b, s, cfg.d_inner).astype(u.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype), params["norm_w"], cfg.norm_eps)
    out = y @ params["out_proj"]
    k = cfg.ssm_conv - 1
    conv_tail = xbc_raw[:, -k:, :] if s >= k else jnp.pad(xbc_raw, ((0, 0), (k - s, 0), (0, 0)))
    new_state = {"conv": conv_tail.astype(state["conv"].dtype), "h": h_final}
    return shard(out, "batch", "seq", "act_d_model"), new_state


def mamba2_decode(params: dict, u: jax.Array, cfg, state: dict) -> tuple[jax.Array, dict]:
    """One-token recurrent step. u: (B, 1, d_model)."""
    b = u.shape[0]
    zxbcdt = u[:, 0] @ params["in_proj"]  # (B, ·)
    din = cfg.d_inner
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    h = cfg.ssm_nheads
    z = zxbcdt[:, :din]
    xbc_new = zxbcdt[:, din : din + din + 2 * g * n]
    dt = zxbcdt[:, -h:]

    # conv ring buffer: window = [conv_state, xbc_new]
    window = jnp.concatenate([state["conv"], xbc_new[:, None, :]], axis=1)  # (B, K, D)
    w = params["conv_w"]  # (K, D)
    conv_out = jnp.einsum("bkd,kd->bd", window.astype(jnp.float32), w.astype(jnp.float32))
    conv_out = jax.nn.silu(conv_out + params["conv_b"].astype(jnp.float32)).astype(u.dtype)
    x = conv_out[:, :din].reshape(b, h, cfg.ssm_headdim)
    b_in = conv_out[:, din : din + g * n].reshape(b, g, n)
    c_in = conv_out[:, din + g * n :].reshape(b, g, n)
    rep = h // g
    b_r = jnp.repeat(b_in, rep, axis=1).astype(jnp.float32)  # (B, H, N)
    c_r = jnp.repeat(c_in, rep, axis=1).astype(jnp.float32)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B, H)
    decay = jnp.exp(dt * params["a_log"])[..., None, None]  # (B, H, 1, 1)
    h_new = state["h"] * decay + dt[..., None, None] * (
        x.astype(jnp.float32)[..., :, None] * b_r[:, :, None, :]
    )
    y = jnp.einsum("bhn,bhpn->bhp", c_r, h_new)  # (B, H, P)
    y = y + params["d_skip"][None, :, None] * x.astype(jnp.float32)
    y = y.reshape(b, din).astype(u.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype), params["norm_w"], cfg.norm_eps)
    out = (y @ params["out_proj"])[:, None]
    new_state = {"conv": window[:, 1:].astype(state["conv"].dtype), "h": h_new}
    return shard(out, "batch", "seq", "act_d_model"), new_state
