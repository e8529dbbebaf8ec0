"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization and only then builds the mesh.

Mesh geometry (TPU v5e targets):
  single-pod : (16, 16)    axes ("data", "model")      = 256 chips
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

"model" is the tensor-parallel axis = the paper's *instance* granularity
(one TP group of chips serves one model replica); "data"/"pod" enumerate
instances and batch shards.  The autoscaling data plane multicasts parameter
blocks along the data axis (chains of collective_permutes) and Fig.14
sharded transfers use the model axis as the scale-up domain.
"""

from __future__ import annotations

import jax


def make_mesh_compat(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis typed Auto (sharding propagated by
    the compiler), which the model's logical-axis rules assume."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(shape)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("pod", "data", "model")[1:]
    return make_mesh_compat(shape, axes)


def make_host_mesh(model: int | None = None) -> jax.sharding.Mesh:
    """A small mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    model = model or 1
    assert n % model == 0
    return make_mesh_compat((n // model, model), ("data", "model"))
