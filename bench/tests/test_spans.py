"""The engine-span reduction (``bench/lib/spans.py``): exact on hand-made
spans, a partition of ``idle_frac.in_step`` on the recorded v5e fixture,
and read end to end from a traced run of a tiny cell on the CPU."""

import gc
import gzip
import json
import os

import jax
import pytest

from bench.lib import spans, spec
from bench.lib import trace as tr
from bench.tests import tiny

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "trace_v5e.json.gz")


def _hand():
    """Two steps: an admission then a decode, and a decode alone."""
    program = [
        ("engine.admit", 0.1, 2.0, {"queued": 1, "free": 2, "admitted": 1}),
        ("engine.prefill", 0.2, 1.5, {"rid": 1, "prompt_len": 8}),
        ("engine.readback", 1.2, 1.5, {"syncs": 1, "tokens": 1}),
        ("engine.splice", 1.6, 1.9, {"rid": 1, "slot": 0, "ops": 4}),
        ("engine.decode", 2.0, 2.2, {"live": 2}),
        ("engine.readback", 2.2, 3.5, {"syncs": 2, "tokens": 2}),
        ("engine.retire", 3.6, 3.8, {"finished": 1, "ops": 1}),
        ("engine.decode", 5.0, 5.1, {"live": 1}),
        ("engine.readback", 5.1, 7.5, {"syncs": 1, "tokens": 1}),
    ]
    ops = [("a", 0.5, 1.0), ("b", 2.1, 3.0), ("c", 5.05, 7.0)]
    host = [("bench.window", 0.0, 10.0), ("engine.step", 0.0, 4.0), ("engine.step", 5.0, 8.0)]
    return tr.Trace({"TPU:0": {"ops": ops, "modules": []}}, host, (0.0, 10.0)), program


def test_innermost_span_names_each_instant():
    _, program = _hand()
    segs = spans.innermost(program[:4])
    assert segs == [(0.1, 0.2, "engine.admit"), (0.2, 1.2, "engine.prefill"),
                    (1.2, 1.5, "engine.readback"), (1.5, 1.6, "engine.admit"),
                    (1.6, 1.9, "engine.splice"), (1.9, 2.0, "engine.admit")]


def test_idle_by_innermost_exact():
    t, program = _hand()
    by, total = spans.idle_by_innermost(t, program)
    assert total == pytest.approx(7.0)
    want = {"": 0.1 + 0.1 + 0.2 + 0.5, "engine.admit": 0.3, "engine.prefill": 0.5,
            "engine.readback": 0.3 + 0.5 + 0.5, "engine.splice": 0.3, "engine.decode": 0.15,
            "engine.retire": 0.2}
    assert set(by) == set(want)
    for k, v in want.items():
        assert by[k] == pytest.approx(v), k
    shares = spans.in_step_shares(t, program)
    assert shares == pytest.approx({"readback": 100 * 1.3 / 7, "dispatch": 100 * 0.65 / 7,
                                    "update": 100 * 0.5 / 7, "other": 100 * 1.2 / 7})


def test_shares_partition_idle_in_step():
    t, program = _hand()
    shares = spans.in_step_shares(t, program)
    assert sum(shares.values()) == pytest.approx(100.0 * tr.idle_in_spans(t, "engine.step"))
    # with no program spans, all of it is "other"
    none = spans.in_step_shares(t, [])
    assert none["other"] == pytest.approx(100.0 * tr.idle_in_spans(t, "engine.step"))
    assert none["readback"] == none["dispatch"] == none["update"] == 0.0


def test_queue_and_sync_readers_exact():
    program = [("engine.enqueue", 0.0, 0.0, {"rid": 1, "depth": 1}),
               ("engine.enqueue", 0.5, 0.5, {"rid": 2, "depth": 2}),
               ("engine.enqueue", 0.7, 0.7, {"rid": 3, "depth": 3}),  # never admitted
               ("engine.prefill", 0.2, 0.4, {"rid": 1, "prompt_len": 8}),
               ("engine.prefill", 1.5, 1.7, {"rid": 2, "prompt_len": 8})]
    assert spans.queue_waits(program) == pytest.approx([0.2, 1.0])
    assert spans.engine_queue_p90_ms(program) == pytest.approx(920.0)
    _, hand = _hand()
    assert spans.host_syncs_per_token(hand) == 1.0
    assert spans.host_syncs_per_token([("engine.readback", 0, 1, {"syncs": 1, "tokens": 4})]) == 0.25
    assert spans.host_syncs_per_token([]) is None
    assert spans.engine_queue_p90_ms([]) is None


def test_recorded_v5e_trace_has_no_program_spans():
    with gzip.open(FIXTURE, "rt") as f:
        t = tr.Trace.from_json(json.load(f))
    idle = tr.idle_in_spans(t, "engine.step")
    shares = spans.in_step_shares(t, [])
    assert sum(shares.values()) == pytest.approx(100.0 * idle)
    assert shares["other"] == pytest.approx(100.0 * idle)
    m = spans.metrics(t, [])
    assert set(m) == {f"idle_frac.in_step.{g}" for g in spans.IN_STEP_GROUPS}


def test_tool_reads_engine_spans_of_a_traced_run(tmp_path):
    root = tiny.make_root(str(tmp_path))
    tiny.peaks_for_cpu(root, jax.devices()[0].device_kind)
    tool = spec.load_module(os.path.join(spec.BENCH_DIR, "tools", "engine_spans.py"))
    load, hooks = tr.load, list(gc.callbacks)
    r = tool.run("tiny-gqa.open", 2**31 + 77, 1.0, require_tpu=False, root=root)
    assert tr.load is load and gc.callbacks == hooks
    assert r["correct"] is True
    es = r["engine_spans"]
    assert es["metrics"]["host_syncs_per_token"] == 1.0
    assert es["metrics"]["engine_queue_p90_ms"] >= 0.0
    n = es["span_counts"]
    assert n["engine.enqueue"] == n["engine.prefill"] == n["engine.splice"] == r["attempted"]
    assert n["engine.readback"] == n["engine.prefill"] + n["engine.decode"]
    assert "python.gc" not in n and es["longest_gaps"] == []  # no device plane on the CPU


def test_longest_gaps_placed_and_named():
    t, program = _hand()
    gaps = spans.longest_gaps(t, program, 0.0, 10.0, top=3)
    assert [(g["start"], g["end"]) for g in gaps] == [(7.0, 10.0), (3.0, 5.05), (1.0, 2.1)]
    assert gaps[0]["host"] == pytest.approx({"engine.step": 1.0})
    assert gaps[0]["engine"] == pytest.approx({"engine.readback": 0.5, "": 2.5})
    assert gaps[1]["host"] == pytest.approx({"engine.step": 1.0 + 0.05})
    assert gaps[1]["engine"] == pytest.approx({"engine.readback": 0.5, "engine.retire": 0.2,
                                               "engine.decode": 0.05, "": 1.3})
    assert gaps[2]["engine"] == pytest.approx({"engine.prefill": 0.2, "engine.readback": 0.3,
                                               "engine.admit": 0.2, "engine.splice": 0.3,
                                               "engine.decode": 0.1, "": 0.0})
