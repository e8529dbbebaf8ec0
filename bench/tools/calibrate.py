"""Readings that define a cell, taken once on the chip in one process.

    python3 bench/tools/calibrate.py granite-8b.code --unloaded --sweep 1.2,1.6,2.0 --schedules 1,2,3
    python3 bench/tools/calibrate.py granite-8b.code --limits 101,102,103 --control 101,102,103

--unloaded   TTFT of one request at the mix's 90th-percentile prompt, and the
             decode step of one request, on an idle engine: the cell's SLO
             limits are 5x these.
--sweep      open-loop windows at the given rates, once on each schedule of
             ``--schedules`` (the mix's ``schedule_seed`` replaced): p90
             TTFT and TPOT, failures, and whether the admission queue grew.
--auto-rate  the knee: the highest swept rate at which every schedule met
             the TTFT limit with a queue that did not grow; the cell's rate
             is 4/5 of it, and --limits runs at that rate.
--limits     for each seed: weights from that seed, a short window at the
             cell's load, the widest logit gap of the served tokens against
             the float32 reference (the number ``correct`` compares).
--control    for each seed: the same, with the reference rounded to int8 and
             to fp8 in the program's place (the control that must fail),
             and the verdict ``correct`` would give it at the cell's limit.
--dump-trace a short traced window: the trace's planes and lines, and a
             trimmed record for the tests' fixture.

Results go to standard output as JSON lines and to ``--out`` (bench_out/).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import numpy as np  # noqa: E402

from bench.lib import check, client, harness, spec, stats, traffic  # noqa: E402
from bench.lib import trace as tr  # noqa: E402
from bench.reference.common import Reference  # noqa: E402



def emit(rec: dict, out: str) -> None:
    print(json.dumps(rec), flush=True)
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "calibrate.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\n")


def reseed(server, cell, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from bench.lib.weights import seed_words

    drv = cell.driver()
    build = drv.param_builder(cell.config, cell.reference(), server.mcfg)
    server.engine.params = None
    server.params = None
    server.params = build(jnp.asarray(seed_words(seed)))
    server.engine.params = server.params
    jax.block_until_ready(server.params)


def unloaded(server, cell, vocab: int) -> dict:
    mix = cell.traffic
    lens = sorted(r.prompt_len for r in traffic.generate(mix, dict(cell.cell, rate_rps=100), 10.0))
    p90 = lens[int(0.9 * (len(lens) - 1))]
    ttft = []
    for i in range(5):
        r = traffic.Request(2_000_000 + i, p90, 2, 0.0)
        w = client.run_window(server, [r], seconds=0.0, vocab=vocab, seed=1)
        ttft.append(w.records[0].stamps[0] - w.records[0].submit)
    r = traffic.Request(2_000_100, lens[0], min(120, int(cell.cell["engine"]["max_seq"]) - lens[0]), 0.0)
    w = client.run_window(server, [r], seconds=0.0, vocab=vocab, seed=1)
    steps = [s.t1 - s.t0 for s in w.steps if not s.admitted]
    rec = {"cell": cell.name, "mode": "unloaded", "p90_prompt": p90,
           "ttft_ms": [t * 1e3 for t in ttft], "ttft_median_ms": stats.median(ttft) * 1e3,
           "decode_step_median_ms": stats.median(steps) * 1e3,
           "slo_ttft_ms": 5 * stats.median(ttft) * 1e3, "slo_tpot_ms": 5 * stats.median(steps) * 1e3}
    return rec


def window(server, cell, seed: int, seconds: float, vocab: int, rate=None, schedule=None,
           drain_s=60.0):
    c = dict(cell.cell)
    if rate is not None:
        c["rate_rps"] = rate
    mix = dict(cell.traffic)
    if schedule is not None:
        mix["schedule_seed"] = schedule
    reqs = traffic.generate(mix, c, seconds)
    clients = int(mix["clients"]) if mix["arrivals"] == "closed" else None
    return client.run_window(server, reqs, seconds=seconds, vocab=vocab, seed=seed,
                             clients=clients, drain_s=drain_s), len(reqs)


def sweep_point(server, cell, rate: float, schedule: int, seconds: float, vocab: int) -> dict:
    w, n = window(server, cell, 4242, seconds, vocab, rate=rate, schedule=schedule, drain_s=30.0)
    recs = [r for r in w.records if r.due < seconds]
    ttft = [(r.stamps[0] - r.due) * 1e3 for r in recs if r.stamps]
    tpot = [(r.stamps[-1] - r.stamps[0]) * 1e3 / (len(r.stamps) - 1) for r in recs
            if r.done and len(r.stamps) > 1]
    half = seconds / 2
    qw = [(w.steps[r.admit_step].t0 - r.due) for r in recs if r.admit_step >= 0]
    first = [q for q, r in zip(qw, [r for r in recs if r.admit_step >= 0]) if r.due < half]
    second = [q for q, r in zip(qw, [r for r in recs if r.admit_step >= 0]) if r.due >= half]
    return {"cell": cell.name, "mode": "sweep", "rate": rate, "schedule": schedule,
            "seconds": seconds, "due": n,
            "finished": sum(r.done for r in recs),
            "ttft_p50_ms": stats.percentile(ttft, 50) if ttft else None,
            "ttft_p90_ms": stats.percentile(ttft, 90) if ttft else None,
            "tpot_p90_ms": stats.percentile(tpot, 90) if tpot else None,
            "queue_wait_mean_first_half_ms": 1e3 * float(np.mean(first)) if first else None,
            "queue_wait_mean_second_half_ms": 1e3 * float(np.mean(second)) if second else None,
            "drained_at": w.drained_at,
            "out_tok_s": sum(1 for r in w.records for t in r.stamps if t <= seconds) / seconds}


def gap_reading(server, cell, seed: int, seconds: float, vocab: int, controls: list[str]) -> dict:
    reseed(server, cell, seed)
    w, n = window(server, cell, seed, seconds, vocab)
    recs_w = [r for r in w.records if r.due < seconds]
    ck = cell.cell["check"]
    limit = float(ck["limit"])
    recs = check.sample(recs_w, seed, int(ck["min_tokens"]), int(ck["max_requests"]))
    served = [list(server.tokens(r.handle)) for r in recs]
    attn = cell.reference()
    t = time.perf_counter()
    ref = Reference(cell.config, attn)
    g = check.served_gaps(ref, seed, recs, served, vocab)
    out = {"cell": cell.name, "mode": "limits", "seed": seed, "due": n,
           "finished": sum(r.done for r in recs_w), "sampled": len(recs),
           "served_tokens": int(g.size), "widest_gap": check.widest_gap(g),
           "gap_p99": float(np.percentile(g, 99)), "n_mismatch": int((g > 0).sum()),
           "reference_s": time.perf_counter() - t,
           "lengths": [[r.req.prompt_len, r.req.n_out] for r in recs]}
    for fmt in controls:
        t = time.perf_counter()
        cg = check.control_gaps(ref, Reference(cell.config, attn, control=fmt), seed, recs, served, vocab)
        out[f"control_{fmt}_widest_gap"] = check.widest_gap(cg)
        out[f"control_{fmt}_correct"] = check.verdict(check.widest_gap(cg), 0, limit)
        out[f"control_{fmt}_n_mismatch"] = int((cg > 0).sum())
        out[f"control_{fmt}_s"] = time.perf_counter() - t
    return out


def dump_trace(server, cell, vocab: int, out: str) -> None:
    from jax.profiler import ProfileData
    import glob
    import jax

    d = tempfile.mkdtemp(prefix="bench_calib_trace_")
    c = dict(cell.cell)
    mix = cell.traffic
    reqs = traffic.generate(mix, c, 4.0)
    clients = int(mix["clients"]) if mix["arrivals"] == "closed" else None
    with tr.capture(d):
        with jax.profiler.TraceAnnotation("bench.window"):
            w = client.run_window(server, reqs, seconds=4.0, vocab=vocab, seed=99,
                                  clients=clients, drain_s=5.0, annotate=True)
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    print(f"trace file {os.path.getsize(path)} bytes", flush=True)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("PLANE", plane.name, lines[:12], flush=True)
        for ln in plane.lines:
            if ln.name in (tr.OP_LINE, tr.MODULE_LINE, "Steps", "XLA TraceMe"):
                evs = list(ln.events)[:6]
                print("   ", ln.name, [(e.name, e.start_ns, e.duration_ns) for e in evs], flush=True)
        if plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                ours = [(e.name, e.start_ns, e.duration_ns) for e in ln.events if e.name in tr.SPANS][:4]
                if ours:
                    print("    host line", ln.name, ours, flush=True)
    t = tr.load(d)
    hi = 0.2
    small = tr.Trace({k: {kk: [e for e in vv if e[1] < hi] for kk, vv in v.items()}
                      for k, v in t.devices.items()},
                     [s for s in t.spans if s[1] < hi], (0.0, hi))
    os.makedirs(out, exist_ok=True)
    import gzip

    with gzip.open(os.path.join(out, f"trace_{cell.name}.json.gz"), "wt") as f:
        json.dump(small.to_json(), f)
    print("steps in first second:", [(s.t0, s.t1, s.prefill_lens, len(s.decode_lens))
                                      for s in w.steps if s.t0 < hi][:40], flush=True)
    print("module counts:", {k: tr.module_seconds(t, k) for k in ("_prefill_one", "_decode_all")},
          "prefills", sum(len(s.prefill_lens) for s in w.steps),
          "decodes", sum(1 for s in w.steps if s.decode_lens), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--unloaded", action="store_true")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--sweep-seconds", type=float, default=51.0)
    ap.add_argument("--schedules", default="", help="schedule seeds of the sweep (default: the mix's)")
    ap.add_argument("--limits", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--controls", default="int8,fp8")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--dump-trace", action="store_true")
    ap.add_argument("--auto-rate", action="store_true",
                    help="after the sweep, run --limits at 4/5 of the knee it found")
    ap.add_argument("--out", default=os.path.join(spec.ROOT, "bench_out"),
                    help="directory for the records")
    ap.add_argument("--root", default=spec.ROOT, help="a checkout other than this one (tests)")
    ap.add_argument("--cpu", action="store_true", help="rehearse on the CPU")
    a = ap.parse_args()

    cell = spec.load_cell(a.workload, root=a.root)
    info, dev = harness.device_info(cell.chips, require_tpu=not a.cpu)
    if not a.cpu:
        harness.compile_cache_dir()
    vocab = int(cell.config["vocab_size"])
    t = time.perf_counter()
    server = cell.driver().Server(cell, 1)
    lengths = traffic.used_prompt_lengths(cell.traffic, cell.cell, 51.0)
    harness.warm(server, lengths, vocab, server.n_slots)
    emit({"cell": cell.name, "mode": "setup", "seconds": time.perf_counter() - t, "device": info}, a.out)
    if a.dump_trace:
        dump_trace(server, cell, vocab, a.out)
    slo = dict(cell.cell.get("slo") or {})
    if a.unloaded:
        u = unloaded(server, cell, vocab)
        emit(u, a.out)
        slo = {"ttft_ms": u["slo_ttft_ms"], "tpot_ms": u["slo_tpot_ms"]}
    schedules = [int(x) for x in a.schedules.split(",") if x] or [int(cell.traffic["schedule_seed"])]
    points = []
    for x in [float(x) for x in a.sweep.split(",") if x]:
        for sch in schedules:
            points.append(sweep_point(server, cell, x, sch, a.sweep_seconds, vocab))
            emit(points[-1], a.out)
    if a.auto_rate and points:
        def met(p):
            q1, q2 = p["queue_wait_mean_first_half_ms"], p["queue_wait_mean_second_half_ms"]
            return (p["ttft_p90_ms"] is not None and p["ttft_p90_ms"] <= slo["ttft_ms"]
                    and q1 is not None and q2 is not None and q2 <= max(2 * q1, q1 + 250.0))

        rates = sorted({p["rate"] for p in points})
        ok = [r for r in rates if all(met(p) for p in points if p["rate"] == r)]
        knee = max(ok) if ok else min(rates)
        cell.cell["rate_rps"] = round(0.8 * knee, 2)
        emit({"cell": cell.name, "mode": "knee", "slo": slo, "knee": knee, "rate_rps": cell.cell["rate_rps"],
              "met": ok}, a.out)
    ctrl = {int(x) for x in a.control.split(",") if x}
    seeds = [int(x) for x in a.limits.split(",") if x]
    for s in seeds + sorted(ctrl - set(seeds)):
        emit(gap_reading(server, cell, s, a.seconds, vocab,
                         a.controls.split(",") if s in ctrl else []), a.out)
    stats_mem = dev.memory_stats() or {}
    emit({"cell": cell.name, "mode": "memory", "peak_bytes_in_use": stats_mem.get("peak_bytes_in_use"),
          "bytes_limit": stats_mem.get("bytes_limit")}, a.out)


if __name__ == "__main__":
    main()
