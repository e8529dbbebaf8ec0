"""Whole runs of the harness on the CPU at tiny sizes: the engine's tokens
against the float32 reference for both attention kinds, the faults the
check must catch, and the command's refusal to run without a TPU."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench.lib import harness, spec
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tiny.make_root(str(tmp_path_factory.mktemp("bench")))
    tiny.peaks_for_cpu(r, jax.devices()[0].device_kind)
    return r


@pytest.mark.parametrize("workload", ["tiny-gqa.open", "tiny-gqa.closed", "tiny-mla.open",
                                      "tiny-mla.closed"])
def test_engine_tokens_match_reference(root, workload):
    r = harness.run(workload, 2**31 + 12345, 1.5, trace=False, require_tpu=False, root=root)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["window_compiles"] == 0
    assert list(r)[-1] == "checks"
    names = {m["name"] for m in spec.load_cell(workload, root=root).end_to_end}
    assert set(r["metrics"]) == names


def _stale_state(server):
    eng, orig = server.engine, server.engine._decode_all

    def decode(params, last, caches, live):
        nxt, _ = orig(params, last, caches, live)
        return nxt, caches  # the step returns its cache unchanged

    eng._decode_all = decode


def _half_batch(server):
    eng, orig = server.engine, server.engine._decode_all

    def decode(params, last, caches, live):
        half = jnp.arange(live.shape[0]) < live.shape[0] // 2
        return orig(params, last, caches, live & half)  # upper slots left out

    eng._decode_all = decode


def _altered_token(server):
    eng, orig = server.engine, server.engine.step
    n = {"steps": 0}

    def step():
        out = orig()
        n["steps"] += 1
        if n["steps"] % 5 == 0:
            for req in eng.active.values():
                req.out_tokens[-1] = (req.out_tokens[-1] + 1) % eng.cfg.vocab_size
        return out

    eng.step = step


@pytest.mark.parametrize("fault", [_stale_state, _half_batch, _altered_token])
@pytest.mark.parametrize("workload", ["tiny-gqa.open", "tiny-mla.closed"])
def test_faults_make_correct_false(root, workload, fault):
    r = harness.run(workload, 424242, 1.5, trace=False, require_tpu=False, root=root,
                    server_hook=fault)
    assert r["correct"] is False
    assert r["checks"]["widest_gap"]["value"] > r["checks"]["widest_gap"]["limit"]


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(spec.BENCH_DIR, "run.py"), "--workload",
                        "granite-8b.code", "--seed", "1", "--seconds", "1"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
