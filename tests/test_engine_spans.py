"""The engine's profiler spans and counters, recorded on the CPU and read
back with the benchmark's own trace readers (``bench.lib.trace`` and
``bench.lib.spans``); the jitted steps' program names the ``mfu.*``
readers match; and the colocated CLI's TTFT stamp."""

import argparse
import os
import re
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve
from repro.models import transformer as TF
from repro.serving.engine import InstanceEngine, ServeRequest
from repro.serving.router import Router

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import spans, spec  # noqa: E402
from bench.lib import trace as tr  # noqa: E402

CFG = get_config("granite-8b", reduced=True)
PARAMS = TF.init_params(jax.random.PRNGKey(0), CFG)


def _recorded(tmp, body):
    """Run ``body`` inside a ``bench.window`` span under the profiler and
    return the benchmark's record of the trace and the engine's spans."""
    with tr.capture(str(tmp)):
        with jax.profiler.TraceAnnotation("bench.window"):
            body()
    return tr.load(str(tmp)), spans.load(str(tmp))


def _reqs(n, seed=0):
    rng = np.random.default_rng(seed)
    return [ServeRequest(100 + i, rng.integers(0, CFG.vocab_size, size=8).astype(np.int32),
                         max_new_tokens=2 + i % 3) for i in range(n)]


@pytest.fixture(scope="module")
def colocated(tmp_path_factory):
    eng = InstanceEngine(CFG, PARAMS, n_slots=2, max_seq=32)
    reqs = _reqs(5)  # more requests than slots: queueing and slot reuse

    def body():
        for r in reqs:
            eng.submit(r)
        while eng.active or eng.queue:
            with jax.profiler.TraceAnnotation("engine.step"):
                eng.step()

    trace, program = _recorded(tmp_path_factory.mktemp("trace"), body)
    return eng, reqs, trace, program


def _by(program, name):
    return [p for p in program if p[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_every_admitted_request_has_nested_spans(colocated):
    eng, reqs, trace, program = colocated
    steps = [s for s in trace.spans if s[0] == "engine.step"]
    admits = _by(program, "engine.admit")
    readbacks = _by(program, "engine.readback")
    for r in reqs:
        (enq,) = [p for p in _by(program, "engine.enqueue") if p[3]["rid"] == r.rid]
        (pre,) = [p for p in _by(program, "engine.prefill") if p[3]["rid"] == r.rid]
        (spl,) = [p for p in _by(program, "engine.splice") if p[3]["rid"] == r.rid]
        assert pre[3]["prompt_len"] == len(r.prompt)
        assert spl[3]["slot"] in range(eng.n_slots) and spl[3]["ops"] >= 3
        assert enq[2] <= pre[1] and pre[2] <= spl[1]
        (child,) = [b for b in readbacks if _inside(b, pre)]
        assert child[3] == {"syncs": 1, "tokens": 1}
        (adm,) = [a for a in admits if _inside(pre, a)]
        assert _inside(spl, adm)
        assert any(_inside(adm, s) for s in steps)
    for name in ("engine.decode", "engine.retire"):
        assert all(any(_inside(p, s) for s in steps) for p in _by(program, name))
    # queued/free at entry, admitted at exit
    assert sum(a[3]["admitted"] for a in admits) == len(reqs)
    assert all(a[3]["admitted"] == min(a[3]["queued"], a[3]["free"]) for a in admits)


def test_span_args_add_up_to_the_counters(colocated):
    """One device-to-host transfer per decode step and one per prefill,
    whatever the number of live slots: each decode's readback carries one
    sync and a token for each of its live slots."""
    eng, reqs, _, program = colocated
    rb = _by(program, "engine.readback")
    n_syncs = eng.stats.decode_steps + len(reqs)  # one prefill per request
    assert sum(p[3]["syncs"] for p in rb) == eng.stats.host_syncs == n_syncs
    assert sum(p[3]["tokens"] for p in rb) == eng.stats.tokens == sum(len(r.out_tokens) for r in reqs)
    decodes = _by(program, "engine.decode")
    prefills = _by(program, "engine.prefill")
    step_rb = [b for b in rb if not any(_inside(b, p) for p in prefills)]
    assert len(step_rb) == len(decodes)
    for d, b in zip(decodes, step_rb):
        assert d[2] <= b[1] and b[3] == {"syncs": 1, "tokens": d[3]["live"]}
    assert max(d[3]["live"] for d in decodes) == eng.n_slots
    assert spans.host_syncs_per_token(program) == n_syncs / eng.stats.tokens < 1.0
    assert eng.stats.decode_steps == len(_by(program, "engine.decode"))
    assert eng.stats.admitted == len(_by(program, "engine.splice")) == len(reqs)
    assert sum(p[3]["finished"] for p in _by(program, "engine.retire")) == len(reqs)
    assert all(p[3]["live"] >= 1 for p in _by(program, "engine.decode"))
    assert spans.engine_queue_p90_ms(program) >= 0.0


def test_decode_span_carries_the_live_context(colocated):
    """``ctx_tokens`` of each decode is the positions its live slots attend
    over: a request's j-th decode (j = 1 .. tokens - 1) attends over its
    prompt and j served tokens."""
    eng, reqs, _, program = colocated
    decodes = _by(program, "engine.decode")
    want = sum(len(r.prompt) + j for r in reqs for j in range(1, len(r.out_tokens)))
    assert sum(p[3]["ctx_tokens"] for p in decodes) == eng.stats.ctx_tokens == want
    assert all(p[3]["ctx_tokens"] >= 9 * p[3]["live"] for p in decodes)  # prompts of 8


@pytest.mark.parametrize("arch", ["granite-8b", "minicpm3-4b"])
def test_cache_bytes_counts_the_slot_caches_once(arch):
    """GQA holds K and V per KV head; MLA the latent and the shared rope key;
    both hold one int32 length per slot and layer."""
    cfg = get_config(arch, reduced=True)
    eng = InstanceEngine(cfg, TF.init_params(jax.random.PRNGKey(0), cfg), n_slots=3, max_seq=32)
    if cfg.attn == "mla":
        per_position = (cfg.kv_lora_rank + cfg.qk_rope_dim) * 2
    else:
        per_position = 2 * cfg.n_kv_heads * cfg.resolved_head_dim * 2
    rows = cfg.n_layers * 3
    assert eng.stats.cache_bytes == rows * 32 * per_position + rows * 4
    assert eng.stats.cache_bytes == sum(x.nbytes for x in jax.tree.leaves(eng.caches))


def test_disagg_admit_prefilled_emits_splice(tmp_path):
    pre = InstanceEngine(CFG, PARAMS, n_slots=1, max_seq=32)
    dec = InstanceEngine(CFG, PARAMS, n_slots=2, max_seq=32)
    (req,) = _reqs(1, seed=3)

    def body():
        first, one = pre.prefill_only(req)
        assert dec.admit_prefilled(req, first, one)

    _, program = _recorded(tmp_path, body)
    (p,) = _by(program, "engine.prefill")
    (s,) = _by(program, "engine.splice")
    assert p[3]["rid"] == s[3]["rid"] == req.rid and p[2] <= s[1]
    assert pre.stats.host_syncs == pre.stats.tokens == 1 and pre.stats.admitted == 0
    assert dec.stats.admitted == 1 and dec.stats.host_syncs == 0


@pytest.mark.parametrize("metric", ["mfu.decode", "mfu.prefill"])
def test_jitted_steps_keep_the_module_names_the_mfu_readers_match(metric):
    eng = InstanceEngine(CFG, PARAMS, n_slots=2, max_seq=32)
    fragment = spec.load_module(os.path.join(spec.BENCH_DIR, "metrics", metric + ".py")).PROGRAM
    if fragment == "_decode_all":
        lowered = eng._decode_all.lower(PARAMS, eng.last_tokens, eng.caches, eng.slot_live)
    else:
        lowered = eng._prefill_one.lower(PARAMS, np.zeros((1, 8), np.int32))
    assert "HloModule jit_" + fragment + "," in lowered.compile().as_text()


def _eqns_outside_scans(jaxpr):
    """Equations of ``jaxpr`` and of the calls it makes, not entering a scan."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "scan":
            continue
        for sub in eqn.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from _eqns_outside_scans(sub)


def test_decode_step_merges_no_whole_cache_leaf_after_the_layer_scan():
    """The decode appends inside the layer scan and hands the caches back as
    they come out of it: no select over a whole stacked cache leaf (a
    live-slot merge) runs after the scan."""
    eng = InstanceEngine(CFG, PARAMS, n_slots=3, max_seq=32)
    jaxpr = jax.make_jaxpr(eng._decode_all)(PARAMS, eng.last_tokens, eng.caches, eng.slot_live)
    leaf_shapes = {x.shape for x in jax.tree.leaves(eng.caches) if x.ndim >= 2 and x.shape[1] == 3}
    eqns = list(_eqns_outside_scans(jaxpr.jaxpr))
    assert any(e.primitive.name == "scan" for e in eqns) and len(leaf_shapes) == 2
    selects = [v.aval.shape for e in eqns if e.primitive.name == "select_n" for v in e.outvars]
    assert selects and not leaf_shapes & set(selects)


def test_colocated_cli_stamps_ttft_at_the_first_token(monkeypatch, capsys):
    events = []
    note_first, note_done = Router.note_first_token, Router.note_done

    def first(self, rid, now):
        events.append(("first", rid))
        note_first(self, rid, now)

    def done(self, rid):
        events.append(("done", rid))
        note_done(self, rid)

    monkeypatch.setattr(Router, "note_first_token", first)
    monkeypatch.setattr(Router, "note_done", done)
    args = argparse.Namespace(requests=4, prompt_len=8, gen_len=4, n_slots=2, seed=0)
    finished = serve.run_colocated(args, CFG, PARAMS)
    assert len(finished) == 4
    firsts = [rid for kind, rid in events if kind == "first"]
    assert sorted(firsts) == sorted(r.rid for r in finished)  # once each
    # both slots deliver a first token a step after admission and 3 steps
    # before they finish
    assert [k for k, _ in events[:2]] == ["first", "first"]
    out = capsys.readouterr().out
    # one host sync per decode step and per prefill, over the 4 x 4 tokens served
    admitted = int(re.search(r"admitted (\d+) ", out).group(1))
    steps = int(re.search(r"decode_steps (\d+) ", out).group(1))
    assert admitted == 4 and steps >= 6
    assert f"host_syncs/token {(steps + admitted) / 16:.2f} " in out
    assert "ctx_tokens " in out and "cache_bytes " in out
