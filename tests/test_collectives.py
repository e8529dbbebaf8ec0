"""TPU data-plane collectives validated numerically on 8 host devices.

Runs in a subprocess because --xla_force_host_platform_device_count must be
set before jax initializes (the main pytest process has 1 device).
"""

import os
import subprocess
import sys
import textwrap

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_chain_broadcast_delivers_to_all_ranks():
    """The pipelined ppermute chain broadcast (Fig. 13a TPU adaptation):
    the vector starts on rank 0 only, and every rank's OWN copy ends equal
    to it (not rank 0's view of it)."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.collectives import chain_broadcast
        from repro.launch.mesh import make_mesh_compat

        mesh = make_mesh_compat((8,), ("chain",))
        src = jax.device_put(jnp.arange(1000, dtype=jnp.float32), jax.devices()[0])
        out = chain_broadcast(src, mesh, "chain", n_blocks=4)
        assert out.shape == (8, 1000)
        shards = sorted(out.addressable_shards, key=lambda s: s.index[0].start)
        assert [s.device for s in shards] == list(mesh.devices.flat)
        for rank, shard in enumerate(shards):
            assert shard.index[0] == slice(rank, rank + 1)
            np.testing.assert_array_equal(np.asarray(shard.data)[0], np.arange(1000))
        print("ok")
    """)


def test_chain_broadcast_step_count_matches_pipelining_model():
    from repro.core.collectives import pipelined_chain_steps

    # Fig. 13a: n_blocks + n_ranks - 2 forwarding steps, not n_blocks*(R-1)
    assert pipelined_chain_steps(16, 8) == 16 + 7 - 1
    assert pipelined_chain_steps(16, 2) < 16 * 1 + 8


def test_chain_broadcast_seconds_independent_of_ranks():
    from repro.core.collectives import chain_broadcast_seconds

    t2 = chain_broadcast_seconds(16e9, 12.5e9, n_blocks=64, n_ranks=2)
    t8 = chain_broadcast_seconds(16e9, 12.5e9, n_blocks=64, n_ranks=8)
    assert t8 / t2 < 1.15  # ~independent of receiver count (pipelined)


def test_sharded_group_transfer_allgather():
    """Fig. 14: each source device ships a 1/g shard one chain hop; the
    target scale-up domain AllGathers to reconstruct the full block."""
    _run("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.collectives import sharded_group_transfer
        from repro.launch.mesh import make_mesh_compat

        mesh = make_mesh_compat((2, 4), ("chain", "scaleup"))
        full = jnp.arange(64, dtype=jnp.float32)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P(None, "scaleup"),), out_specs=P("chain", None),
                           check_vma=False)
        def xfer(shard):
            out = sharded_group_transfer(shard[0], "scaleup", "chain", 0, 1)
            return out[None]

        # each scaleup rank of chain-rank 0 holds a distinct 16-elem shard
        out = xfer(full.reshape(1, 64))
        got = np.asarray(out)[1]  # chain rank 1 view
        np.testing.assert_allclose(got, np.arange(64))
        print("ok")
    """)
