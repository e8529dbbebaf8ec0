import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run (deliverable e): lower + compile every assigned
(architecture x input-shape) cell on the production meshes and extract the
roofline terms from the compiled artifact.

The three lines above MUST stay first: jax locks the device count at first
initialization, and the 512 placeholder host devices exist only for this
script — smoke tests and benchmarks see 1 device.  The process is pinned to
the CPU so that it never takes an accelerator that another process holds.

Per cell this script records (one JSON per cell under --out):
  * per-device parameter/cache/argument/temp bytes (memory_analysis → proves
    the program fits the 16 GB/chip v5e budget),
  * XLA cost_analysis flops/bytes (raw) and loop-corrected dot FLOPs, HBM
    bytes and collective bytes (repro.launch.hlo_analysis — XLA counts scan
    bodies once, so raw numbers undercount by ~n_layers),
  * the collective op/byte breakdown (drives §Perf),
  * the three §Roofline terms against TPU v5e constants.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                    # all cells, both meshes
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --list
"""

import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, cells, get_config
from repro.launch import steps as steps_mod
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell, input_specs  # noqa: F401 (public API)

# TPU v5e roofline constants (per chip)
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def _sharded_bytes(abstract_tree, sharding_tree) -> int:
    """Per-device bytes of an abstract pytree under the given shardings."""
    total = 0
    leaves = jax.tree.leaves(abstract_tree)
    shards = jax.tree.leaves(sharding_tree, is_leaf=lambda x: hasattr(x, "spec"))
    for sds, sh in zip(leaves, shards):
        shape = sh.shard_shape(sds.shape) if hasattr(sh, "shard_shape") else sds.shape
        total += int(np.prod(shape)) * sds.dtype.itemsize
    return total


def model_flops(arch: str, shape: str) -> float:
    """MODEL_FLOPS: 6*N*D for training, 2*N_active*D for inference (global)."""
    cfg = get_config(arch)
    sp = SHAPES[shape]
    n_active = cfg.approx_active_params()
    if sp.kind == "train":
        return 6.0 * n_active * sp.seq_len * sp.global_batch
    if sp.kind == "prefill":
        return 2.0 * n_active * sp.seq_len * sp.global_batch
    # decode: one token per sequence
    return 2.0 * n_active * sp.global_batch


def run_cell(arch: str, shape: str, multi_pod: bool, outdir: str, *, force: bool = False) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    path = os.path.join(outdir, f"{arch}__{shape}__{mesh_name}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    rec: dict = {"arch": arch, "shape": shape, "mesh": mesh_name, "chips": n_chips}
    t0 = time.perf_counter()
    try:
        art = build_cell(arch, shape, mesh)
        with mesh:
            jitted = jax.jit(
                art.fn,
                in_shardings=art.in_shardings,
                out_shardings=art.out_shardings,
                donate_argnums=art.donate,
            )
            lowered = jitted.lower(*art.args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        for attr in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            rec[attr] = int(getattr(mem, attr, 0) or 0)
        cost = compiled.cost_analysis() or {}
        rec["raw_flops"] = float(cost.get("flops", 0.0))
        rec["raw_bytes"] = float(cost.get("bytes accessed", 0.0))

        rep = analyze(compiled.as_text())
        rec["dot_flops_per_dev"] = rep.dot_flops
        rec["hbm_bytes_per_dev"] = rep.hbm_bytes
        rec["collective_bytes_per_dev"] = rep.collective_bytes
        rec["coll_by_op"] = dict(rep.coll_by_op)
        rec["coll_count"] = {k: int(v) for k, v in rep.coll_count.items()}

        # per-device input footprints (weights / caches)
        rec["param_bytes_per_dev"] = _sharded_bytes(art.args[0], art.in_shardings[0])
        if SHAPES[shape].kind in ("decode", "long_decode"):
            rec["cache_bytes_per_dev"] = _sharded_bytes(art.args[2], art.in_shardings[2])
        elif SHAPES[shape].kind == "prefill":
            rec["cache_bytes_per_dev"] = _sharded_bytes(art.args[-1], art.in_shardings[-1])

        # roofline terms (seconds per step, per chip)
        rec["t_compute"] = rep.dot_flops / PEAK_FLOPS
        rec["t_memory"] = rep.hbm_bytes / HBM_BW
        rec["t_collective"] = rep.collective_bytes / ICI_BW
        terms = {"compute": rec["t_compute"], "memory": rec["t_memory"],
                 "collective": rec["t_collective"]}
        rec["bottleneck"] = max(terms, key=terms.get)
        mf = model_flops(arch, shape)
        rec["model_flops_global"] = mf
        hlo_global = rep.dot_flops * n_chips
        rec["useful_flop_frac"] = mf / hlo_global if hlo_global else 0.0
        rec["lower_s"] = t_lower
        rec["compile_s"] = t_compile
        rec["ok"] = True
    except Exception as e:  # record the failure — dry-run failures are bugs
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]

    os.makedirs(outdir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def _fmt(rec: dict) -> str:
    if not rec.get("ok"):
        return (f"FAIL {rec['arch']:18s} {rec['shape']:12s} {rec['mesh']:6s} "
                f"{rec.get('error', '?')[:90]}")
    return (
        f"ok   {rec['arch']:18s} {rec['shape']:12s} {rec['mesh']:6s} "
        f"args/dev={rec['argument_size_in_bytes']/2**30:6.2f}GiB "
        f"temp/dev={rec['temp_size_in_bytes']/2**30:6.2f}GiB "
        f"t_comp={rec['t_compute']*1e3:8.2f}ms t_mem={rec['t_memory']*1e3:8.2f}ms "
        f"t_coll={rec['t_collective']*1e3:8.2f}ms [{rec['bottleneck']}] "
        f"compile={rec['compile_s']:.0f}s"
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + ["all"],
                    help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + ["all"])
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=os.path.abspath(DEFAULT_OUT))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true", help="list cells and exit")
    args = ap.parse_args()

    grid = [
        (a, s)
        for a, s, ok in cells()
        if (args.arch in (None, "all", a)) and (args.shape in (None, "all", s))
    ]
    if args.list:
        for a, s in grid:
            print(a, s)
        return

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    n_fail = 0
    for a, s in grid:
        for mp in meshes:
            rec = run_cell(a, s, mp, args.out, force=args.force)
            print(_fmt(rec), flush=True)
            n_fail += 0 if rec.get("ok") else 1
    print(f"\n{len(grid) * len(meshes) - n_fail}/{len(grid) * len(meshes)} cells passed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
