"""A cell, a configuration, a traffic mix and a metric added as files only
are found by name: no existing file of the harness is edited."""

import json
import os

import jax

from bench.lib import harness, spec
from bench.tests import tiny

DUMMY_METRIC = '''
def read(ctx):
    return float(len(ctx.window_steps))
'''


def test_new_files_are_found_by_name(tmp_path):
    metric = {"name": "dummy.steps", "unit": "steps", "better": "higher", "source": "host_clock",
              "layer": "engine", "moves": "tpot_p90_ms", "workloads": ["tiny-dummy.new-mix"]}
    root = tiny.make_root(str(tmp_path), extra_per_layer=[metric])
    tiny.peaks_for_cpu(root, jax.devices()[0].device_kind)
    bench = os.path.join(root, "bench")
    before = {p: open(os.path.join(bench, p), "rb").read() for p in
              ("lib/harness.py", "lib/spec.py", "lib/traffic.py", "drivers/instance_engine.py")}
    # the new files
    with open(os.path.join(bench, "metrics", "dummy.steps.py"), "w") as f:
        f.write(DUMMY_METRIC)
    with open(os.path.join(bench, "configs", "tiny-dummy.json"), "w") as f:
        json.dump(dict(tiny.GQA, name="tiny-dummy", num_hidden_layers=1), f)
    with open(os.path.join(bench, "traffic", "new-mix.json"), "w") as f:
        json.dump(dict(tiny.TRAFFIC["open"], prompt=dict(tiny.TRAFFIC["open"]["prompt"], grid=[16])), f)
    with open(os.path.join(bench, "cells", "tiny-dummy.new-mix.json"), "w") as f:
        json.dump(tiny.cell_file(rate=5.0, slots=2), f)
    # and their entries in BENCHMARK.json
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny-dummy", "source": "test", "file": "bench/configs/tiny-dummy.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny-dummy.new-mix", "config": "tiny-dummy", "traffic": "new-mix",
                           "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in ("setup_s", "tpot_p90_ms"):
            m["workloads"] = m["workloads"] + ["tiny-dummy.new-mix"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    cell = spec.load_cell("tiny-dummy.new-mix", root=root)
    assert cell.config["num_hidden_layers"] == 1 and cell.traffic["prompt"]["grid"] == [16]
    assert [m["name"] for m in cell.per_layer] == ["dummy.steps"]
    r = harness.run("tiny-dummy.new-mix", 7, 1.0, trace=True, require_tpu=False, root=root)
    assert r["correct"] is True
    assert r["metrics"]["dummy.steps"]["value"] > 0
    for p, data in before.items():
        assert open(os.path.join(bench, p), "rb").read() == data


def test_unknown_names_are_errors(tmp_path):
    root = tiny.make_root(str(tmp_path))
    try:
        spec.load_cell("no-such.cell", root=root)
    except spec.SpecError as e:
        assert "no-such.cell" in str(e)
    else:
        raise AssertionError("an unknown workload was accepted")
    try:
        harness.peaks_for("TPU v99", os.path.join(root, "bench"))
    except KeyError as e:
        assert "TPU v99" in str(e)
    else:
        raise AssertionError("an unknown device kind was accepted")
