"""Benchmark orchestrator: one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig17] [--smoke]

Each module prints a markdown table, writes CSV/JSON under
benchmarks/results/, and asserts its paper-headline property.  ``--smoke``
(also ``BLITZ_SMOKE=1``) runs every suite on a tiny config with headline
assertions relaxed — the CI job that keeps benchmark scripts from rotting."""

from __future__ import annotations

import argparse
import os
import time
import traceback

SUITES = [
    ("fig3_slo_vs_speed", "Fig.3 SLO attainment vs scaling-stop duration"),
    ("fig17_e2e_traces", "Fig.17 TTFT/TBT: blitz vs S-LLM vs AllCache"),
    ("fig18_gpu_time", "Fig.18 GPU time vs DistServe full/half"),
    ("fig19_cache_usage", "Fig.19 O(1) host cache vs S-LLM"),
    ("fig20_ablation", "Fig.20 +Network/+Multicast/+ZigZag ablation"),
    ("fig21_live_timeline", "Fig.21 live-scale throughput timeline"),
    ("net_contention", "Flow-level data plane: contended/degraded links"),
    ("net_scale", "Fleet-scale FlowSim: incremental engine vs full solve"),
    ("plan_generation", "§5.1/5.2 plan-gen + ZigZag solver latency"),
    ("kernel_micro", "App.A kernel micro (Pallas vs oracle)"),
    ("roofline", "§Roofline table from dry-run artifacts"),
    ("disagg_e2e", "disagg vs colocated on real engines"),
    ("maas_gpu_time", "MaaS fleet sharing vs static (Fig.18 claim)"),
    ("obs_overhead", "tracing overhead + recorded sim perf baseline"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single suite by name")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny configs, relaxed assertions (CI smoke job)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        os.environ["BLITZ_SMOKE"] = "1"  # read by benchmarks.common.smoke()

    failures = []
    suite_wall: dict[str, float] = {}
    for name, desc in SUITES:
        if args.only and args.only not in name:
            continue
        print(f"\n{'='*78}\n== {name}: {desc}\n{'='*78}", flush=True)
        t0 = time.perf_counter()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
            suite_wall[name] = time.perf_counter() - t0
            print(f"-- {name} ok in {suite_wall[name]:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
            print(f"-- {name} FAILED", flush=True)

    print(f"\n{'='*78}")
    if suite_wall and not args.only:
        # per-suite wall seconds are themselves a tracked perf surface
        from benchmarks.common import bench_record

        bench_record(
            "suite_times",
            {f"{k}.wall_s": v for k, v in suite_wall.items()},
        )
    if failures:
        print(f"{len(failures)} suite(s) failed: {failures}")
        raise SystemExit(1)
    print("all benchmark suites passed")


if __name__ == "__main__":
    main()
