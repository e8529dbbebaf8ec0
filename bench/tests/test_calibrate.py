"""The calibration tool on the CPU at a tiny size, and the limit of a
cell's check, set from the program's and the controls' readings."""

import json
import os
import subprocess
import sys

import jax

from bench.lib import spec
from bench.tests import tiny
from bench.tools.set_limit import readings


def test_calibrate_sweeps_schedules_and_reads_controls(tmp_path):
    root = tiny.make_root(str(tmp_path))
    tiny.peaks_for_cpu(root, jax.devices()[0].device_kind)
    out = str(tmp_path / "out")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(spec.BENCH_DIR, "tools", "calibrate.py"),
                        "tiny-gqa.open", "--cpu", "--root", root, "--unloaded", "--sweep", "4,8",
                        "--schedules", "1,2", "--sweep-seconds", "1", "--auto-rate",
                        "--limits", "11,12,13", "--control", "11,12,13", "--seconds", "1",
                        "--out", out], capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    recs = [json.loads(l) for l in open(os.path.join(out, "calibrate.jsonl"))]
    sweep = [(r["rate"], r["schedule"]) for r in recs if r["mode"] == "sweep"]
    assert sweep == [(4.0, 1), (4.0, 2), (8.0, 1), (8.0, 2)]
    knee = [r for r in recs if r["mode"] == "knee"][0]
    assert knee["rate_rps"] == round(0.8 * knee["knee"], 2)
    lim = [r for r in recs if r["mode"] == "limits"]
    assert [r["seed"] for r in lim] == [11, 12, 13]
    assert all(r["finished"] == r["due"] for r in lim)
    assert all(f"control_{f}_correct" in r for r in lim for f in ("int8", "fp8"))


def _rec(seed, gap, **controls):
    return {"seed": seed, "widest_gap": gap, **{f"control_{k}_widest_gap": v for k, v in controls.items()}}


def test_upper_is_the_least_control_that_separates():
    recs = [_rec(1, 0.10, int8=2.5, fp8=0.9), _rec(2, 0.12, int8=2.6, fp8=1.1),
            _rec(3, 0.08, int8=2.55, fp8=0.95), _rec(4, 0.11)]
    r = readings(recs)
    assert r["lower"] == 0.12
    assert r["upper"] == 0.9 and r["upper_from"] == "fp8"
    assert 0.12 < r["limit"] < 0.9
    assert r["limit"] == float(f"{0.12**0.4 * 0.9**0.6:.4g}")
    for c in r["controls"].values():
        assert c["correct"] == [False, False, False]


def test_a_control_that_does_not_separate_is_passed_over():
    recs = [_rec(1, 0.10, int8=2.5, fp8=0.2), _rec(2, 0.12, int8=2.6, fp8=0.5),
            _rec(3, 0.08, int8=2.55, fp8=0.3)]
    r = readings(recs)
    assert r["upper"] == 2.5 and r["upper_from"] == "int8"
    assert r["controls"]["int8"]["correct"] == [False, False, False]


def test_no_limit_without_three_separating_readings():
    assert readings([_rec(1, 0.1, int8=2.0), _rec(2, 0.1, int8=2.0)])["limit"] is None
    assert readings([_rec(s, 0.1, int8=0.2) for s in (1, 2, 3)])["limit"] is None
