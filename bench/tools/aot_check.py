"""Compile a cell's programs for a described TPU v5e, with no chip attached,
and print what each would hold in device memory.

    JAX_PLATFORMS=cpu python3 bench/tools/aot_check.py minicpm3-4b.longctx [...]

For each cell: the weight-drawing program, the decode step at the cell's
slots and cache length, and the prefill at the cell's longest prompt, each
through the same jitted function the engine runs.  A program that does not
fit, or that the TPU compiler refuses, raises here and costs no chip time.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench.lib import spec, traffic  # noqa: E402


def main(cells: list[str]) -> None:
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    for name in cells:
        cell = spec.load_cell(name)
        drv = cell.driver()
        mcfg = drv.model_config(cell.config)
        build = drv.param_builder(cell.config, cell.reference(), mcfg)
        eng = cell.cell["engine"]
        n, smax = int(eng["n_slots"]), int(eng["max_seq"])

        def report(label, fn, *args):
            t = time.time()
            c = jax.jit(fn).lower(*args).compile()
            m = c.memory_analysis()
            print(f"{name} {label}: compile {time.time() - t:.1f} s, args {m.argument_size_in_bytes}, "
                  f"out {m.output_size_in_bytes}, temp {m.temp_size_in_bytes}, "
                  f"alias {m.alias_size_in_bytes}", flush=True)
            return m

        words = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
        report("weights", build, words)
        params = jax.tree.map(sds, jax.eval_shape(build, words))
        from repro.models import transformer as TF

        caches = jax.tree.map(sds, TF.init_caches(mcfg.replace(uniform_decode=False), n, smax, abstract=True))
        cfg = mcfg.replace(uniform_decode=False)

        def decode_all(params, last, caches, live):
            nxt, new = TF.decode_step(cfg, params, last, caches)
            sel = lambda a, b: jnp.where(live.reshape((1, n) + (1,) * (a.ndim - 2)), a, b) \
                if a.ndim >= 2 and a.shape[1] == n else a
            return jnp.where(live, nxt, last), jax.tree.map(sel, new, caches)

        vec = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=chip)
        live = jax.ShapeDtypeStruct((n,), bool, sharding=chip)
        report(f"decode x{n} @ {smax}", decode_all, params, vec, caches, live)
        longest = max(traffic.used_prompt_lengths(cell.traffic, cell.cell, 51.0))

        def prefill_one(params, tokens):
            return TF.prefill(cfg, params, tokens, TF.init_caches(cfg, 1, smax))

        toks = jax.ShapeDtypeStruct((1, longest), jnp.int32, sharding=chip)
        report(f"prefill {longest}", prefill_one, params, toks)


if __name__ == "__main__":
    main(sys.argv[1:])
