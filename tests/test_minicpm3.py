"""MiniCPM3's muP scalings (embeddings, residual branches, logits) against a
plain float32 reference, through ``InstanceEngine`` and through the
live-scaling layer split; and the programs of models that leave the
scalings at 1.0, unchanged by them."""

import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.configs import get_config
from repro.core.live_scaling import cooperative_forward
from repro.models import layers
from repro.models import transformer as TF
from repro.serving.engine import InstanceEngine, ServeRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.reference import mla as ref_mla  # noqa: E402
from bench.reference.common import ein, rmsnorm  # noqa: E402

CFG = get_config("minicpm3-4b", reduced=True)
PARAMS = TF.init_params(jax.random.PRNGKey(0), CFG)
SCALINGS = ("embed_scale", "residual_scale", "logit_scale")

# Largest |program - reference| logit, in units of the reference logits'
# standard deviation.  The program runs in bf16 (weights, activations and
# the residual stream rounded to 8 bits of mantissa), the reference in
# float32 at HIGHEST precision: at this size the program reads 0.15-0.22.
# Dropping any one scaling reads 1.7 or more (logit_scale alone: 30).
TOL = 0.5


def reference_logits(cfg, params, tokens):
    """MiniCPM3 in float32 from the program's weights: embeddings x
    embed_scale; per layer x + s * attn(rmsnorm(x)), x + s * swiglu(rmsnorm(x))
    with s = residual_scale; logits = (rmsnorm(x) x logit_scale) @ unembed.
    tokens: (S,) -> logits (S, vocab)."""
    c = {"rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
         "qk_nope_head_dim": cfg.qk_nope_dim, "qk_rope_head_dim": cfg.qk_rope_dim,
         "num_attention_heads": cfg.n_heads}
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    x = f32(params["embed"]["tok"])[tokens] * cfg.embed_scale
    positions = jnp.arange(len(tokens))
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: f32(a[i]), params["layers"])
        attn_w = {"attn." + k: v for k, v in lp["attn"].items()}
        h = rmsnorm(x, lp["norm1"], cfg.norm_eps)
        x = x + ref_mla.attention(c, attn_w, h, positions) * cfg.residual_scale
        h = rmsnorm(x, lp["norm2"], cfg.norm_eps)
        mlp = lp["mlp"]
        hidden = jax.nn.silu(ein("sd,df->sf", h, mlp["w_gate"])) * ein("sd,df->sf", h, mlp["w_up"])
        x = x + ein("sf,fd->sd", hidden, mlp["w_down"]) * cfg.residual_scale
    h = rmsnorm(x, f32(params["final_norm"]), cfg.norm_eps) * cfg.logit_scale
    return np.asarray(ein("sd,dv->sv", h, f32(params["embed"]["unembed"])))[:, : cfg.vocab_size]


def _gap(prog, ref):
    return float(np.max(np.abs(prog - ref)) / np.std(ref))


def _program(dropped):
    return CFG if dropped is None else CFG.replace(**{dropped: 1.0})


@pytest.mark.parametrize("dropped", [None, *SCALINGS])
def test_engine_prefill_then_decode_logits_match_the_reference(dropped, monkeypatch):
    """Two requests admitted in one step, then decoded together: the
    logits of each prefill and of each live slot's decode, read from the
    engine's own programs, against the reference teacher-forced on the
    served tokens.  The reference keeps every scaling; the program drops
    ``dropped``, and then the comparison must fail."""
    rows = []
    unembed = layers.unembed

    def recording_unembed(p, x, cfg):
        out = unembed(p, x, cfg)
        jax.debug.callback(lambda a: rows.append(np.asarray(a, np.float32)), out)
        return out

    monkeypatch.setattr(layers, "unembed", recording_unembed)
    eng = InstanceEngine(_program(dropped), PARAMS, n_slots=2, max_seq=48)
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(i, rng.integers(0, CFG.vocab_size, size=n).astype(np.int32), 6)
            for i, n in enumerate((12, 20))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    prefills, decodes = rows[:2], rows[2:]  # one (1, 1, V) each, then (2, 1, V) a step
    assert [p.shape[0] for p in prefills] == [1, 1] and len(decodes) == 5

    gaps = []
    for r, pre in zip(reqs, prefills):
        prog = np.concatenate([pre[0]] + [d[r.slot] for d in decodes])[:, : CFG.vocab_size]
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1], np.int32)])
        ref = reference_logits(CFG, PARAMS, jnp.asarray(seq))[len(r.prompt) - 1:]
        assert prog.shape == ref.shape == (6, CFG.vocab_size)
        gaps.append(_gap(prog, ref))
    if dropped is None:
        assert max(gaps) < TOL, gaps
    else:
        assert max(gaps) > TOL, gaps


@pytest.mark.parametrize("dropped", [None, *SCALINGS])
def test_live_scaling_split_matches_monolithic_and_reference(dropped):
    """``cooperative_forward`` (layers [0, k) then [k, L)) equals the
    monolithic forward for every split, and both match the reference with
    the scalings on; with one dropped from the program, they do not."""
    cfg = _program(dropped)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, CFG.vocab_size, (1, 20)), jnp.int32)
    full = np.asarray(TF.train_forward(cfg, PARAMS, tokens)[0], np.float32)
    for k in range(cfg.n_layers + 1):
        split = np.asarray(cooperative_forward(cfg, PARAMS, tokens, k), np.float32)
        np.testing.assert_array_equal(split, full)
    ref = reference_logits(CFG, PARAMS, tokens[0])
    gap = _gap(full[0, :, : CFG.vocab_size], ref)
    assert (gap < TOL) if dropped is None else (gap > TOL), gap


# Multiplies each scaling adds to an engine program when it is not 1.0:
# the embedding, the two residual branches of the (scanned) layer body, the
# LM head's input.
SITES = {"embed_scale": 1, "residual_scale": 2, "logit_scale": 1}


def _program_structure(cfg, program):
    """Count of each primitive in an engine program's jaxpr, and the scalar
    literals its ``mul``s take, nested programs (scan, jit) included."""
    params = TF.init_params(jax.random.PRNGKey(0), cfg)
    eng = InstanceEngine(cfg, params, n_slots=2, max_seq=32)
    if program == "_decode_all":
        closed = jax.make_jaxpr(eng._decode_all)(params, eng.last_tokens, eng.caches, eng.slot_live)
    else:
        closed = jax.make_jaxpr(eng._prefill_one)(params, np.zeros((1, 8), np.int32))
    prims, literals = collections.Counter(), []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            prims[eqn.primitive.name] += 1
            if eqn.primitive.name == "mul":
                literals.extend(float(v.val) for v in eqn.invars if isinstance(v, jcore.Literal))
            for p in eqn.params.values():
                for q in p if isinstance(p, (tuple, list)) else (p,):
                    if isinstance(q, jcore.ClosedJaxpr):
                        walk(q.jaxpr)
                    elif isinstance(q, jcore.Jaxpr):
                        walk(q)

    walk(closed.jaxpr)
    return prims, literals


@pytest.mark.parametrize("arch", ["granite-8b", "minicpm3-4b"])
@pytest.mark.parametrize("program", ["_decode_all", "_prefill_one"])
def test_default_scalings_leave_the_lowered_programs_unchanged(arch, program):
    """A GQA and an MLA model with every scaling at 1.0: no multiply by 1.0
    anywhere in the engine's programs, and setting one scaling adds exactly
    its multiplies and nothing else, so at 1.0 the programs are those of a
    model without the scalings."""
    base = get_config(arch, reduced=True).replace(**{s: 1.0 for s in SCALINGS})
    prims, literals = _program_structure(base, program)
    assert 1.0 not in literals
    for scaling, n in SITES.items():
        scaled, scaled_literals = _program_structure(base.replace(**{scaling: 3.0}), program)
        assert scaled - prims == collections.Counter(mul=n) and not prims - scaled, scaling
        assert scaled_literals.count(3.0) - literals.count(3.0) == n, scaling
