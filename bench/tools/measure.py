"""Repeated runs of one cell, each a process of its own, and their spread.

    python3 bench/tools/measure.py granite-8b.code --seeds 1,2,3,4,5,6 --sets 2 \
        --trace-seeds 7,8,9 --seconds 51

Runs ``bench/run.py`` once per seed in each set (the same seeds in every
set), then once per trace seed with ``--trace 1``.  For every metric it
prints each set's values, median and quartile spread ((Q3 - Q1) / median,
quartiles as ``statistics.quantiles`` gives them), the set's spread with
its run farthest from the median left out, and the wider of the sets'
spreads times five: the bound those runs would support.  This process never
imports JAX, so each child gets the chip.  Records go to ``--out`` (bench_out/).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.lib.stats import spread  # noqa: E402


def run_one(workload: str, seed: int, seconds: float, trace: int, out: str) -> dict:
    t = time.time()
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload", workload,
                        "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
                       capture_output=True, text=True, cwd=ROOT)
    rec = {"workload": workload, "seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": time.time() - t,
           "stderr_tail": [l for l in p.stderr.splitlines() if l.startswith("[bench]")][-8:]}
    lines = p.stdout.strip().splitlines()
    if p.returncode == 0 and lines:
        rec["result"] = json.loads(lines[-1])
    else:
        rec["stderr_end"] = p.stderr[-3000:]
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"measure_{workload}.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\n")
    r = rec.get("result", {})
    print(json.dumps({"seed": seed, "trace": trace, "rc": p.returncode, "wall_s": round(rec["wall_s"], 1),
                      "correct": r.get("correct"), "failed": r.get("failed"),
                      "metrics": {k: v["value"] for k, v in r.get("metrics", {}).items()},
                      "checks": r.get("checks"), "window_compiles": r.get("window_compiles"),
                      "mem": r.get("device", {}).get("memory_peak_bytes"),
                      "busy_s": r.get("device", {}).get("busy_s"),
                      "window_s": r.get("device", {}).get("window_s")}), flush=True)
    if p.returncode != 0:
        print(rec["stderr_end"], flush=True)
    return rec


def trimmed(values: list[float]) -> list[float]:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return values[:far] + values[far + 1:]


def summarize(sets: list[list[dict]]) -> None:
    names = sorted({k for s in sets for r in s for k in r.get("result", {}).get("metrics", {})})
    for name in names:
        vals = [[r["result"]["metrics"][name]["value"] for r in s
                 if name in r.get("result", {}).get("metrics", {})] for s in sets]
        if any(len(v) < 3 for v in vals):
            continue
        sp = [spread(v) for v in vals]
        tsp = [spread(trimmed(v)) for v in vals]
        allv = [x for v in vals for x in v]
        print(json.dumps({"metric": name, "sets": vals, "medians": [statistics.median(v) for v in vals],
                          "spreads": sp, "trimmed_spreads": tsp, "spread_all": spread(allv),
                          "bound_5x": 5 * max(sp)}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--out", default=os.path.join(ROOT, "bench_out"), help="directory for the records")
    a = ap.parse_args()
    seeds = [int(x) for x in a.seeds.split(",") if x]
    sets = [[run_one(a.workload, s, a.seconds, 0, a.out) for s in seeds] for _ in range(a.sets if seeds else 0)]
    for s in [int(x) for x in a.trace_seeds.split(",") if x]:
        run_one(a.workload, s, a.seconds, 1, a.out)
    if sets:
        summarize(sets)


if __name__ == "__main__":
    main()
