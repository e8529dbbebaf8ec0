"""TPU-native multicast data plane (hardware adaptation of §5.1).

The paper's chain multicast is point-to-point NCCL send/recv.  On TPU the
native neighbour-forwarding primitive is ``jax.lax.ppermute`` inside
``shard_map``; a serial forwarding chain becomes a *pipelined systolic
broadcast*: the source rank injects parameter block ``b`` at step ``b``, and
every step each rank forwards the block it holds to its chain successor.
After ``n_blocks + n_ranks - 2`` steps every rank holds all blocks — the
exact Fig. 13(a) pipelining argument (total time ~ |M|/B, independent of the
receiver count), expressed as a ``lax.scan`` over steps.

Fig. 14's parallel sharded transfer maps to: each of the ``g`` source
devices ships a distinct 1/g parameter shard to its peer (one ppermute),
then the target scale-up domain runs ``lax.all_gather`` over its ICI axis.

Both are validated numerically on 8 host devices in
``tests/test_collectives.py`` (subprocess with
``--xla_force_host_platform_device_count``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Pipelined chain broadcast (serial forwarding multicast, Fig. 13a)
# ---------------------------------------------------------------------------


def chain_broadcast_blocks(
    blocks: jax.Array,  # (n_blocks, block_elems) — valid on rank `src` only
    axis_name: str,
    n_ranks: int,
    src: int = 0,
) -> jax.Array:
    """Inside shard_map: systolic pipelined broadcast along a rank chain.

    Rank order is ``src, src+1, ..., n_ranks-1`` (the planner emits device
    orderings; callers renumber).  Per step each rank forwards its held
    block to its successor while the source injects the next block — hop
    ``h`` of block ``b`` overlaps hop ``h-1`` of block ``b+1``.
    """
    n_blocks = blocks.shape[0]
    rank = jax.lax.axis_index(axis_name)
    chain_pos = rank - src  # position along the chain (0 = source)
    n_steps = n_blocks + n_ranks - 2
    perm = [(i, i + 1) for i in range(n_ranks - 1)]

    def step(carry, s):
        held, out = carry
        # the source injects block s (clamped); everyone else keeps held
        inject = jax.lax.dynamic_index_in_dim(
            blocks, jnp.clip(s, 0, n_blocks - 1), 0, keepdims=False
        )
        cur = jnp.where(chain_pos == 0, inject, held)
        # store: at step s, chain position p holds block (s - p)
        b = s - chain_pos
        valid = (b >= 0) & (b < n_blocks)
        bc = jnp.clip(b, 0, n_blocks - 1)
        old = jax.lax.dynamic_index_in_dim(out, bc, 0, keepdims=False)
        out = jax.lax.dynamic_update_index_in_dim(
            out, jnp.where(valid, cur, old), bc, 0
        )
        # forward to successor
        nxt = jax.lax.ppermute(cur, axis_name, perm)
        return (nxt, out), None

    held0 = jnp.zeros_like(blocks[0])
    out0 = jnp.where(chain_pos == 0, blocks, jnp.zeros_like(blocks))
    (_, out), _ = jax.lax.scan(step, (held0, out0), jnp.arange(n_steps + 1))
    return out


def chain_broadcast(
    src: jax.Array,  # (total_elems,) on the device of chain rank 0 only
    mesh: Mesh,
    axis_name: str,
    n_blocks: int = 16,
) -> jax.Array:
    """Broadcast a flat parameter vector from chain rank 0 to every rank of
    the 1-D ``mesh`` along ``axis_name``.

    The bytes start on rank 0 alone: the other ranks get empty receive
    buffers allocated on their own device, so every byte they end with was
    moved by the chain's ppermutes.  Returns a ``(n_ranks, total)`` array
    sharded ``P(axis_name)``: row ``r`` is rank ``r``'s own copy."""
    devices = list(mesh.devices.flat)
    n_ranks = mesh.shape[axis_name]
    if len(devices) != n_ranks:
        raise ValueError("chain_broadcast takes a 1-D mesh over the chain axis")
    total = src.shape[0]
    sharding = NamedSharding(mesh, P(axis_name))
    rows = [jax.device_put(src, devices[0])[None]] + [
        jnp.zeros((1, total), src.dtype, device=d) for d in devices[1:]
    ]
    stacked = jax.make_array_from_single_device_arrays((n_ranks, total), sharding, rows)
    return _chain_broadcast_fn(mesh, axis_name, n_blocks, total)(stacked)


@functools.lru_cache(maxsize=8)
def _chain_broadcast_fn(mesh: Mesh, axis_name: str, n_blocks: int, total: int):
    """The jitted chain program, built once per (mesh, size) so a repeated
    broadcast reuses its executable."""
    n_ranks = mesh.shape[axis_name]
    pad = (-total) % n_blocks
    padded = total + pad

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis_name),),
        out_specs=P(axis_name),
        check_vma=False,
    )
    def _bcast(row):
        blocks = jnp.pad(row[0], (0, pad)).reshape(n_blocks, padded // n_blocks)
        out = chain_broadcast_blocks(blocks, axis_name, n_ranks)
        return out.reshape(1, padded)[:, :total]

    return jax.jit(_bcast)


# ---------------------------------------------------------------------------
# Parallel sharded transfer (Fig. 14): shard-send + AllGather over scale-up
# ---------------------------------------------------------------------------


def sharded_group_transfer(
    shard: jax.Array,  # this device's 1/g parameter shard (source group)
    scaleup_axis: str,  # the target group's ICI axis
    chain_axis: str,
    src_rank: int = 0,
    dst_rank: int = 1,
) -> jax.Array:
    """Inside shard_map: each source device ships its 1/g shard one hop down
    the chain axis (a single ppermute = the cross-group RDMA links used in
    parallel), then the receiving scale-up domain AllGathers over ICI.

    Returns the *full* parameter block on every device of the target group
    (and garbage elsewhere — callers mask by rank).
    """
    moved = jax.lax.ppermute(shard, chain_axis, [(src_rank, dst_rank)])
    return jax.lax.all_gather(moved, scaleup_axis, axis=0, tiled=True)


# ---------------------------------------------------------------------------
# Analytic timing (used by the simulator's data-plane model)
# ---------------------------------------------------------------------------


def pipelined_chain_steps(n_blocks: int, n_ranks: int) -> int:
    """Number of hop-times for the systolic broadcast (vs n_blocks*(R-1)
    unpipelined)."""
    return n_blocks + max(n_ranks - 1, 1) - 1


def chain_broadcast_seconds(
    model_bytes: int, bottleneck_bytes_per_s: float, n_blocks: int, n_ranks: int
) -> float:
    block_t = model_bytes / n_blocks / bottleneck_bytes_per_s
    return block_t * pipelined_chain_steps(n_blocks, n_ranks)
