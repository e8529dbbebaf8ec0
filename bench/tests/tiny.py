"""A tiny copy of the benchmark for CPU tests: the real harness, drivers,
references and readers, with small configurations and cells added as files."""

from __future__ import annotations

import json
import os
import shutil

from bench.lib import spec

GQA = {
    "name": "tiny-gqa", "attention": "gqa", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "vocab_size": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
}
MLA = {
    "name": "tiny-mla", "attention": "mla", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "vocab_size": 300, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
}
TRAFFIC = {
    "open": {"arrivals": "poisson", "schedule_seed": 1,
             "prompt": {"mean": 24, "sigma": 0.6, "min": 8, "max": 40, "grid": [8, 16, 24, 40]},
             "output": {"mean": 6, "sigma": 0.6, "min": 2, "max": 12}},
    "closed": {"arrivals": "closed", "clients": 4, "schedule_seed": 1,
               "prompt": {"mean": 24, "sigma": 0.6, "min": 8, "max": 40, "grid": [16, 40]},
               "output": {"mean": 6, "sigma": 0.6, "min": 2, "max": 12}},
}


def cell_file(rate=8.0, slots=3, limit=0.05):
    return {"driver": "instance_engine", "engine": {"n_slots": slots, "max_seq": 56},
            "rate_rps": rate, "check": {"limit": limit, "min_tokens": 24,
                                          "max_requests": 6}}


def make_root(tmp: str, extra_per_layer: list | None = None) -> str:
    """A checkout-like directory: BENCHMARK.json plus a copy of bench/ with
    the tiny cells added.  Returns the root."""
    root = os.path.join(tmp, "root")
    bench = os.path.join(root, "bench")
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for c in (GQA, MLA):
        with open(os.path.join(bench, "configs", c["name"] + ".json"), "w") as f:
            json.dump(c, f)
    for name, mix in TRAFFIC.items():
        with open(os.path.join(bench, "traffic", "tiny-" + name + ".json"), "w") as f:
            json.dump(mix, f)
    workloads = []
    for c in (GQA, MLA):
        for t in TRAFFIC:
            w = f"{c['name']}.{t}"
            workloads.append({"name": w, "config": c["name"], "traffic": "tiny-" + t, "chips": 1,
                              "why": "CPU test"})
            with open(os.path.join(bench, "cells", w + ".json"), "w") as f:
                json.dump(cell_file(), f)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    names = [w["name"] for w in workloads]
    e2e = [dict(m, workloads=names) for m in real["end_to_end"]]
    per = [dict(m, workloads=names) for m in real["per_layer"]] + (extra_per_layer or [])
    bench_json = dict(real, configs=[{"name": c["name"], "source": "test", "file":
                                      f"bench/configs/{c['name']}.json", "reduced": [], "why": "test"}
                                     for c in (GQA, MLA)],
                      workloads=workloads, end_to_end=e2e, per_layer=per)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench_json, f)
    return root


def peaks_for_cpu(root: str, kind: str) -> None:
    """The tests run on the CPU, whose kind the table does not hold."""
    p = os.path.join(root, "bench", "peaks.json")
    with open(p) as f:
        t = json.load(f)
    t[kind] = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "source": "test"}
    with open(p, "w") as f:
        json.dump(t, f)
