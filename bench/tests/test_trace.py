"""The trace reduction: exact on hand-made intervals, and sane on the first
0.2 s of a trace recorded on a TPU v5e (``fixtures/trace_v5e.json.gz``,
written by ``bench/tools/calibrate.py --dump-trace``)."""

import gzip
import json
import os

import pytest

from bench.lib import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "trace_v5e.json.gz")


def _hand():
    ops = [("a", 0.0, 1.0), ("b", 0.5, 1.5), ("c", 3.0, 4.0), ("a", 5.0, 6.0)]
    mods = [("jit__decode_all(1)", 0.0, 1.5), ("jit__prefill_one(2)", 3.0, 4.0)]
    spans = [("bench.window", 0.0, 10.0), ("engine.step", 0.0, 2.0),
             ("client.wait", 2.0, 3.0), ("engine.step", 3.0, 4.5), ("client.wait", 6.0, 10.0)]
    return tr.Trace({"TPU:0": {"ops": ops, "modules": mods}}, spans, (0.0, 10.0))


def test_union_busy_and_idle_exact():
    t = _hand()
    assert tr.union(t.devices["TPU:0"]["ops"], 0.0, 10.0) == [(0.0, 1.5), (3.0, 4.0), (5.0, 6.0)]
    assert tr.busy_seconds(t, 0.0, 10.0) == pytest.approx(3.5)
    assert tr.busy_seconds(t, 0.75, 5.5) == pytest.approx(0.75 + 1.0 + 0.5)
    # steps cover [0, 2] and [3, 4.5]: 3.5 s, busy 1.5 + 1.0 of it
    assert tr.idle_in_spans(t, "engine.step") == pytest.approx(1.0 - 2.5 / 3.5)
    assert tr.module_seconds(t, "_decode_all") == (1.5, 1)
    assert tr.module_seconds(t, "_prefill_one") == (1.0, 1)


def test_breakdown_names_gaps_by_host_span():
    b = tr.breakdown(_hand(), 0.0, 10.0)
    assert b["device_ops"][0] == ["a", 2.0]
    # gaps: [1.5,3] (step 0.5 s, wait 1 s), [4,5] (step 0.5 s), [6,10] (wait 4 s)
    assert b["idle_gaps"] == [["client.wait", 4.0], ["client.wait", 1.5], ["engine.step", 1.0]]


def test_recorded_v5e_trace():
    with gzip.open(FIXTURE, "rt") as f:
        t = tr.Trace.from_json(json.load(f))
    lo, hi = t.window
    busy = tr.busy_seconds(t, lo, hi)
    ops = sum(e - s for d in t.devices.values() for _, s, e in d["ops"])
    assert 0.0 < busy <= hi - lo
    assert busy <= ops + 1e-9
    for frag in ("_prefill_one", "_decode_all"):
        secs, n = tr.module_seconds(t, frag)
        assert n > 0 and 0.0 < secs <= hi - lo + 1.0
    idle = tr.idle_in_spans(t, "engine.step")
    assert idle is not None and 0.0 <= idle < 1.0
    b = tr.breakdown(t, lo, hi)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_only_chip_planes_are_devices():
    assert tr.DEVICE_PLANE.match("/device:TPU:0") and tr.DEVICE_PLANE.match("/device:TPU:3")
    assert not tr.DEVICE_PLANE.match("/device:CUSTOM:Megascale Trace")
    assert not tr.DEVICE_PLANE.match("/host:CPU")
