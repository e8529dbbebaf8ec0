"""The plain float32 reference: what every attention kind shares.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST`` (on a TPU
a float32 matmul otherwise runs in bf16 passes).  No cache, no batching, no
kernels, and nothing imported from the program: weights come from the seed
through ``bench.lib.weights``, one layer at a time, so a reference of a
model that fills the chip fits beside its activations.

A layer is ``x + attn(rmsnorm(x)); x + swiglu(rmsnorm(x))``, embeddings are
looked up, and the logits are ``rmsnorm(x) @ unembed`` (untied).  Causal
attention is computed in blocks of query rows so that the score matrix of
one block, not of the whole sequence, is held.
"""

from __future__ import annotations

import contextvars

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.weights import Leaf, draw, draw_rows, seed_words

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows per attention block
PAD = 1024  # sequences are padded to a multiple of this (causal: no effect)


# set while the control's programs are traced: every matmul operand is then
# rounded to that lower precision (see ``lower``)
_LOWER = contextvars.ContextVar("bench_reference_lower", default=None)


def lower(x, fmt: str):
    """Round to int8 or fp8 (e4m3) with one scale for the whole tensor."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if fmt == "int8":
        s = amax / 127.0
        return jnp.round(x / s) * s
    if fmt == "fp8":
        s = amax / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(fmt)


def ein(spec: str, *ops):
    fmt = _LOWER.get()
    if fmt:
        ops = [lower(o, fmt) for o in ops]
    return jnp.einsum(spec, *ops, precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """Rotary embedding, rotating the two halves of the last axis.
    x: (S, ..., D); positions: (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv  # (S, D/2)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def causal_attention(q, k, v, scale):
    """q, k: (S, H, Dk); v: (S, H, Dv) -> (S, H, Dv)."""
    s_len = q.shape[0]
    outs = []
    for lo in range(0, s_len, Q_BLOCK):
        qb = q[lo : lo + Q_BLOCK]
        scores = ein("qhd,khd->hqk", qb, k) * scale
        qpos = jnp.arange(lo, lo + qb.shape[0])[:, None]
        scores = jnp.where(qpos >= jnp.arange(s_len)[None, :], scores, -jnp.inf)
        outs.append(ein("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


def common_leaves(c: dict) -> tuple[dict[str, Leaf], dict[str, Leaf]]:
    """(model leaves, per-layer leaves) that every attention kind has."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    if c.get("tie_word_embeddings"):
        raise ValueError("the reference models untied embeddings only")
    model = {
        "embed.tok": Leaf((v, d), "embed", (1,)),
        "embed.unembed": Leaf((d, v), "matrix", (0,)),
        "final_norm": Leaf((d,), "norm"),
    }
    layer = {
        "norm1": Leaf((d,), "norm"),
        "norm2": Leaf((d,), "norm"),
        "mlp.w_gate": Leaf((d, f), "matrix", (0,)),
        "mlp.w_up": Leaf((d, f), "matrix", (0,)),
        "mlp.w_down": Leaf((f, d), "matrix", (0,)),
    }
    return model, layer


class Reference:
    """Logits of one model at chosen positions, from the seed alone.

    ``attn`` is the attention kind's module: it gives ``leaves(config)`` and
    ``attention(config, weights, h, positions)``.  ``control`` names a lower
    precision ('int8' or 'fp8') in which every matrix product is computed:
    both operands, weights and activations, rounded to it with one scale
    per tensor (the embedding rows too).  That is the control, which the
    check must refuse."""

    def __init__(self, config: dict, attn, *, control: str | None = None):
        self.c = config
        self.attn = attn
        self.control = control
        self.model_leaves, layer = common_leaves(config)
        self.layer_leaves = {**layer, **attn.leaves(config)}
        self.eps = float(config["rms_norm_eps"])
        self._gen_layer = self._jit(self._gen_layer_impl)
        self._layer = self._jit(self._layer_impl)
        self._embed = self._jit(self._embed_impl)
        self._head = self._jit(self._head_impl)

    def _jit(self, fn):
        jitted = jax.jit(fn)

        def call(*args):
            token = _LOWER.set(self.control)  # seen while the call traces
            try:
                return jitted(*args)
            finally:
                _LOWER.reset(token)

        return call

    def _gen_layer_impl(self, words, layer):
        return {n: draw(words, n, leaf, jnp.bfloat16, layer=layer).astype(jnp.float32)
                for n, leaf in self.layer_leaves.items()}

    def _layer_impl(self, lw, x, positions):
        h = rmsnorm(x, lw["norm1"], self.eps)
        x = x + self.attn.attention(self.c, lw, h, positions)
        h = rmsnorm(x, lw["norm2"], self.eps)
        hidden = jax.nn.silu(ein("sd,df->sf", h, lw["mlp.w_gate"])) * ein("sd,df->sf", h, lw["mlp.w_up"])
        return x + ein("sf,fd->sd", hidden, lw["mlp.w_down"])

    def _embed_impl(self, words, tokens):
        x = draw_rows(words, "embed.tok", self.model_leaves["embed.tok"], tokens, jnp.bfloat16)
        x = x.astype(jnp.float32)
        return lower(x, self.control) if self.control else x

    def _head_impl(self, words, x_rows):
        norm = draw(words, "final_norm", self.model_leaves["final_norm"], jnp.bfloat16)
        w = draw(words, "embed.unembed", self.model_leaves["embed.unembed"], jnp.bfloat16)
        return ein("sd,dv->sv", rmsnorm(x_rows, norm.astype(jnp.float32), self.eps), w.astype(jnp.float32))

    def logits(self, seed: int, seqs: list[np.ndarray], rows: list[np.ndarray]) -> list[np.ndarray]:
        """For each token sequence, the float32 logits (len(rows), vocab) at
        the positions ``rows`` (a row at position p predicts token p + 1)."""
        words = jnp.asarray(seed_words(seed))
        xs, poss = [], []
        for seq in seqs:
            n = len(seq)
            n_pad = -(-n // PAD) * PAD
            toks = np.zeros(n_pad, np.int32)
            toks[:n] = seq
            xs.append(self._embed(words, jnp.asarray(toks)))
            poss.append(jnp.arange(n_pad, dtype=jnp.int32))
        for layer in range(int(self.c["num_hidden_layers"])):
            lw = self._gen_layer(words, jnp.int32(layer))
            xs = [self._layer(lw, x, p) for x, p in zip(xs, poss)]
        out = []
        for x, r in zip(xs, rows):
            r_pad = np.zeros(-(-len(r) // 64) * 64, np.int32)
            r_pad[: len(r)] = r
            out.append(np.asarray(self._head(words, x[jnp.asarray(r_pad)]))[: len(r)])
        return out


