"""Compiles for a TPU v5e that is described to the compiler, not attached.

The Pallas kernels at granite-8b widths and the engine's decode step go
through the chip's own compiler, which refuses what interpret mode lets
pass: block shapes off the (8, 128) tiling, kernels that cannot lower, a
program that does not fit the chip's memory.  Nothing runs, so these tests
say nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.  The fixture skips only where the
topology cannot be described.  The persistent compilation cache is off
around these compiles: an entry compiled for a described chip cannot be
read back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.distributed.sharding import _is_spec
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import fused_rmsnorm
from repro.models import transformer as TF
from repro.serving.engine import InstanceEngine

V5E_HBM_BYTES = 16 * 2**30  # one v5e chip (Google Cloud documentation, "TPU v5e")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the TPU library logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # the library is missing or cannot describe v5e
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# granite-8b: 32 query heads, 8 KV heads, head_dim 128, d_model 4096
KERNELS = {
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        [((1, 2048, 32, 128),), ((1, 2048, 8, 128),), ((1, 2048, 8, 128),)],
    ),
    "decode_attention": (
        lambda q, k, v, n: decode_attention(q, k, v, n, interpret=False),
        [((8, 32, 128),), ((8, 8, 2048, 128),), ((8, 8, 2048, 128),), ((8,), jnp.int32)],
    ),
    "fused_rmsnorm": (
        lambda x, w: fused_rmsnorm(x, w, interpret=False),
        [((2048, 4096),), ((4096,),)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e_at_granite_widths(one_chip, name):
    fn, arg_shapes = KERNELS[name]
    args = [_on(one_chip, *a) for a in arg_shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_decode_step_fits_one_v5e(one_chip):
    """The engine's own jitted decode step, granite-8b widths, 2 layers."""
    cfg = get_config("granite-8b", one_chip=True).replace(n_layers=2)
    eng = InstanceEngine(cfg, None, n_slots=8, max_seq=2048)
    params = jax.tree.map(
        lambda s: _on(one_chip, s.shape, s.dtype), TF.param_template(cfg), is_leaf=_is_spec
    )
    on_chip = lambda tree: jax.tree.map(lambda a: _on(one_chip, a.shape, a.dtype), tree)
    compiled = eng._decode_all.lower(
        params, on_chip(eng.last_tokens), on_chip(eng.caches), on_chip(eng.slot_live)
    ).compile()
    mem = compiled.memory_analysis()
    param_bytes = cfg.approx_params() * 2
    assert mem.argument_size_in_bytes >= param_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES
