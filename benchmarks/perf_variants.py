"""§Perf beyond-paper variants: measure optimized configurations against the
committed defaults and write `dryrun/<arch>__<shape>__<mesh>__<variant>.json`.

Variants are knobs the registered configs do NOT enable by default, so the
§Roofline table stays the (already hillclimbed) mainline and this file holds
the opt-in deltas:

  kvq   — int8 KV cache with per-token absmax scales (§Perf C3)
"""

from __future__ import annotations

import json
import os

from benchmarks.common import markdown_table

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "results", "dryrun")

VARIANTS = [
    ("granite-8b", "decode_32k", {"kv_quant": True}, "kvq"),
    ("nemotron-4-340b", "decode_32k", {"kv_quant": True}, "kvq"),
    ("pixtral-12b", "decode_32k", {"kv_quant": True}, "kvq"),
]


def main():
    # run in a subprocess so the 512-device flag is set before jax init
    import subprocess
    import sys
    import textwrap

    rows = []
    for arch, shape, over, tag in VARIANTS:
        out_path = os.path.join(DRYRUN_DIR, f"{arch}__{shape}__single__{tag}.json")
        if not os.path.exists(out_path):
            code = textwrap.dedent(f"""
                import os
                os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=512'
                import json
                import jax
                from repro.launch.mesh import make_production_mesh
                from repro.launch.steps import build_cell
                from repro.launch.hlo_analysis import analyze
                mesh = make_production_mesh()
                art = build_cell({arch!r}, {shape!r}, mesh, cfg_overrides={over!r})
                with mesh:
                    c = jax.jit(art.fn, in_shardings=art.in_shardings,
                                out_shardings=art.out_shardings,
                                donate_argnums=art.donate).lower(*art.args).compile()
                rep = analyze(c.as_text())
                mem = c.memory_analysis()
                rec = {{
                    'arch': {arch!r}, 'shape': {shape!r}, 'variant': {tag!r},
                    't_compute': rep.dot_flops / 197e12,
                    't_memory': rep.hbm_bytes / 819e9,
                    't_collective': rep.collective_bytes / 50e9,
                    'argument_size_in_bytes': int(mem.argument_size_in_bytes),
                    'ok': True,
                }}
                json.dump(rec, open({out_path!r}, 'w'), indent=1)
            """)
            env = dict(os.environ)
            env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
            # the 512-device host mesh lives on the CPU; never reach for an
            # accelerator the parent may hold
            env["JAX_PLATFORMS"] = "cpu"
            r = subprocess.run([sys.executable, "-c", code], env=env,
                               capture_output=True, text=True, timeout=590)
            if r.returncode != 0:
                print(f"{arch} {shape} {tag} FAILED: {r.stderr[-500:]}")
                continue
        with open(out_path) as f:
            rec = json.load(f)
        base_path = os.path.join(DRYRUN_DIR, f"{arch}__{shape}__single.json")
        base = json.load(open(base_path)) if os.path.exists(base_path) else {}
        rows.append([
            arch, shape, tag,
            round(base.get("t_memory", 0) * 1e3, 2),
            round(rec["t_memory"] * 1e3, 2),
            round(base.get("argument_size_in_bytes", 0) / 2**30, 2),
            round(rec["argument_size_in_bytes"] / 2**30, 2),
        ])
    print(markdown_table(
        ["arch", "shape", "variant", "t_mem base(ms)", "t_mem opt(ms)",
         "args base(GiB)", "args opt(GiB)"], rows))
    return rows


if __name__ == "__main__":
    main()
