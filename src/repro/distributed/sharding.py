"""Logical-axis sharding substrate.

Model code never mentions mesh axes directly.  Every tensor dimension is
tagged with a *logical* axis name ('batch', 'heads', 'd_ff', ...), and a
:class:`ShardingRules` object maps logical names onto the mesh axes that are
actually present ('pod', 'data', 'model').  The same model definition then
runs unsharded on one CPU device (smoke tests), TP-sharded on a single pod
(16x16), or pod+data+model sharded on the multi-pod mesh (2x16x16) — only the
rules change.

Three consumers:
  * ``init_from_template``     — materialize real parameter arrays,
  * ``abstract_from_template`` — ShapeDtypeStructs for the dry-run,
  * ``specs_from_template``    — PartitionSpecs for pjit in/out shardings.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Logical axis rules
# ---------------------------------------------------------------------------

# Default logical-axis -> mesh-axis mapping.  A rule value may be a tuple of
# mesh axes (the logical axis is sharded over their product), a single mesh
# axis name, or None (replicated).  Axes absent from the active mesh are
# dropped at resolution time, so the same rules serve 1-device, single-pod and
# multi-pod meshes.
DEFAULT_RULES: dict[str, Any] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "act_d_model": None,
    "act_heads": "model",
    "act_d_ff": "model",
    "act_vocab": "model",
    "kv_seq": None,
    # parameters (tensor-parallel pattern)
    "d_model": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "d_ff": "model",
    "experts": "model",
    "vocab": "model",
    "lora": None,
    "ssm_heads": "model",
    "ssm_state": None,
    "conv": None,
    # KV cache.  Default: shard the *sequence* dim over the model axis
    # (flash-decoding style: every chip scans its cache slice, XLA combines
    # the softmax stats) because most assigned archs have kv_heads that the
    # 16-way model axis does not divide (8/20/40 kv heads).  Archs with
    # divisible kv_heads (olmoe=16, zamba2=32) override to head-sharding.
    "cache_batch": ("pod", "data"),
    "cache_kv_heads": None,
    "cache_seq": "model",
    # layer stacking axis (scan over layers) is never sharded
    "layers": None,
}

# FSDP overlay for >=100B models: weight d_model dims additionally sharded
# over the data axis so resident parameter bytes scale with the full chip
# count (ZeRO-3 style; XLA inserts the per-layer all-gathers).
FSDP_OVERRIDES: dict[str, Any] = {
    "d_model": ("data",),
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """A mesh plus the logical->mesh axis mapping active for this program."""

    mesh: Mesh | None
    rules: Mapping[str, Any] = dataclasses.field(default_factory=lambda: DEFAULT_RULES)

    def with_overrides(self, overrides: Mapping[str, Any] | None) -> "ShardingRules":
        if not overrides:
            return self
        merged = dict(self.rules)
        merged.update(overrides)
        return ShardingRules(self.mesh, merged)

    # -- resolution ---------------------------------------------------------
    def mesh_axes_for(self, logical: str | None) -> tuple[str, ...]:
        if logical is None:
            return ()
        rule = self.rules.get(logical)
        if rule is None:
            return ()
        if isinstance(rule, str):
            rule = (rule,)
        if self.mesh is None:
            return ()
        present = set(self.mesh.axis_names)
        return tuple(a for a in rule if a in present)

    def spec_for(self, logical_axes: Iterable[str | None]) -> P:
        parts: list[Any] = []
        used: set[str] = set()
        for ax in logical_axes:
            mesh_axes = tuple(a for a in self.mesh_axes_for(ax) if a not in used)
            used.update(mesh_axes)
            if len(mesh_axes) == 0:
                parts.append(None)
            elif len(mesh_axes) == 1:
                parts.append(mesh_axes[0])
            else:
                parts.append(mesh_axes)
        # trim trailing Nones — cosmetic, matches PartitionSpec conventions
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def spec_for_shape(
        self, shape: tuple[int, ...], logical_axes: Iterable[str | None]
    ) -> P:
        """Shape-aware resolution: GSPMD/pjit requires every sharded dim to be
        *exactly divisible* by the product of its mesh axes, so per dim we
        keep the longest prefix of the rule's mesh axes that divides the dim
        (dropping from the end).  A dim the rule cannot divide falls back to
        replication — e.g. 20 attention heads on a 16-way 'model' axis, or
        global_batch=1 (long_500k) on the 16-way 'data' axis."""
        parts: list[Any] = []
        used: set[str] = set()
        mesh_sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape)) if self.mesh else {}
        for dim, ax in zip(shape, logical_axes):
            cand = [a for a in self.mesh_axes_for(ax) if a not in used]
            while cand:
                prod = int(np.prod([mesh_sizes[a] for a in cand]))
                if dim % prod == 0:
                    break
                cand.pop()
            used.update(cand)
            if len(cand) == 0:
                parts.append(None)
            elif len(cand) == 1:
                parts.append(cand[0])
            else:
                parts.append(tuple(cand))
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)


_CTX = threading.local()


def current_rules() -> ShardingRules | None:
    return getattr(_CTX, "rules", None)


@contextlib.contextmanager
def use_sharding_rules(rules: ShardingRules | None):
    """Context manager installing ambient sharding rules for model code."""
    prev = getattr(_CTX, "rules", None)
    _CTX.rules = rules
    try:
        yield rules
    finally:
        _CTX.rules = prev


def constrain_layer_params(lp: Any, template: Any) -> Any:
    """Inside a scan-over-layers body, pin the sliced layer parameters to
    their TP-only sharding (d_model replicated).

    §Perf D VERDICT: REFUTED on nemotron-340b train — the constraint forced
    re-gathers in forward, backward AND remat recompute (t_comp 163->292 s,
    temp 45->52.7 GiB) without freeing the hoisted buffer.  Kept as an
    unused utility + the recorded negative result; the 340B-train memory
    story remains multi-pod (batch sharded over pods) per EXPERIMENTS.md."""
    rules = current_rules()
    if rules is None or rules.mesh is None or not rules.mesh_axes_for("d_model"):
        return lp  # no FSDP overlay active
    tp_rules = rules.with_overrides({"d_model": None})

    def one(leaf, spec):
        sharding = NamedSharding(
            tp_rules.mesh, tp_rules.spec_for_shape(tuple(leaf.shape), spec.axes)
        )
        return jax.lax.with_sharding_constraint(leaf, sharding)

    return jax.tree.map(one, lp, template, is_leaf=lambda x: isinstance(x, TensorSpec))


def seq_sharded() -> bool:
    """True when the ambient rules shard the activation 'seq' axis — the
    sequence-parallel mode used by archs whose head counts don't divide the
    model axis (qwen1.5/minicpm3/whisper).  Attention call sites switch to
    an unchunked-q layout so the q shards stay local (see §Perf A2)."""
    rules = current_rules()
    return bool(rules and rules.mesh is not None and rules.mesh_axes_for("seq"))


def resolve_spec(logical_axes: Iterable[str | None], rules: ShardingRules | None = None) -> P:
    rules = rules or current_rules()
    if rules is None:
        return P()
    return rules.spec_for(logical_axes)


def shard(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Apply a sharding constraint expressed in logical axes (no-op without
    ambient rules / mesh — e.g. in single-device smoke tests)."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    spec = rules.spec_for_shape(tuple(x.shape), logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter templates
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    """Declarative parameter leaf: shape + dtype + logical axes + init law."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"  # 'normal' | 'zeros' | 'ones' | 'ssm_a' | 'ssm_dt'
    dtype: Any = jnp.float32
    scale: float | None = None  # stddev override for 'normal'

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_leaf(key: jax.Array, spec: TensorSpec) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "ssm_a":
        # Mamba2 A is a negative scalar per head: A = -exp(uniform(log 1..16))
        u = jax.random.uniform(key, spec.shape, jnp.float32)
        a = -jnp.exp(u * (np.log(16.0) - np.log(1.0)) + np.log(1.0))
        return a.astype(spec.dtype)
    if spec.init == "ssm_dt":
        # dt bias initialised so softplus(dt_bias) spans [1e-3, 1e-1]
        u = jax.random.uniform(key, spec.shape, jnp.float32)
        dt = jnp.exp(u * (np.log(0.1) - np.log(1e-3)) + np.log(1e-3))
        inv = dt + jnp.log(-jnp.expm1(-dt))
        return inv.astype(spec.dtype)
    if spec.init == "normal":
        fan_in = spec.shape[0] if len(spec.shape) >= 2 else max(spec.shape[-1], 1)
        std = spec.scale if spec.scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(spec.dtype)
    raise ValueError(f"unknown init {spec.init!r}")


def _is_spec(x) -> bool:
    return isinstance(x, TensorSpec)


# one compiled program per distinct TensorSpec: XLA fuses the float32
# draw, the scale and the cast, so only the leaf in its own dtype is ever
# allocated on the device (an eager draw of a stacked (L, d, d_ff) leaf
# would hold two float32 temporaries of twice the leaf's size)
_init_leaf_jit = jax.jit(_init_leaf, static_argnums=1)


def init_from_template(key: jax.Array, template: Any) -> Any:
    """Materialize parameter arrays from a TensorSpec pytree."""
    leaves, treedef = jax.tree.flatten(template, is_leaf=_is_spec)
    keys = jax.random.split(key, max(len(leaves), 1))
    arrays = [_init_leaf_jit(k, s) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, arrays)


def abstract_from_template(template: Any, dtype: Any | None = None) -> Any:
    """ShapeDtypeStruct pytree (dry-run stand-ins; never allocates)."""

    def to_sds(s: TensorSpec):
        return jax.ShapeDtypeStruct(s.shape, dtype or s.dtype)

    return jax.tree.map(to_sds, template, is_leaf=_is_spec)


def specs_from_template(template: Any, rules: ShardingRules) -> Any:
    """PartitionSpec pytree matching the template structure (shape-aware)."""

    def to_spec(s: TensorSpec) -> P:
        return rules.spec_for_shape(s.shape, s.axes)

    return jax.tree.map(to_spec, template, is_leaf=_is_spec)


def shardings_from_template(template: Any, rules: ShardingRules) -> Any:
    """NamedSharding pytree (requires rules.mesh)."""
    assert rules.mesh is not None

    def to_sharding(s: TensorSpec) -> NamedSharding:
        return NamedSharding(rules.mesh, rules.spec_for_shape(s.shape, s.axes))

    return jax.tree.map(to_sharding, template, is_leaf=_is_spec)


def specs_for_axes(abstract: Any, axes: Any, rules: ShardingRules) -> Any:
    """PartitionSpec pytree for an abstract (ShapeDtypeStruct) pytree whose
    logical axes are given as a parallel pytree of tuples — used for KV
    caches and batch inputs in the dry-run."""

    def one(sds, ax):
        return rules.spec_for_shape(tuple(sds.shape), ax)

    return jax.tree.map(one, abstract, axes, is_leaf=lambda x: isinstance(x, tuple))


def shardings_for_axes(abstract: Any, axes: Any, rules: ShardingRules) -> Any:
    assert rules.mesh is not None

    def one(sds, ax):
        return NamedSharding(rules.mesh, rules.spec_for_shape(tuple(sds.shape), ax))

    return jax.tree.map(one, abstract, axes, is_leaf=lambda x: isinstance(x, tuple))


def stack_specs(spec: TensorSpec, n: int) -> TensorSpec:
    """Prepend a scan-over-layers axis to a TensorSpec."""
    return dataclasses.replace(
        spec, shape=(n, *spec.shape), axes=("layers", *spec.axes)
    )


def stack_template(template: Any, n: int) -> Any:
    return jax.tree.map(lambda s: stack_specs(s, n), template, is_leaf=_is_spec)


def param_count(template: Any) -> int:
    leaves = jax.tree.leaves(template, is_leaf=_is_spec)
    return int(sum(int(np.prod(s.shape)) for s in leaves))


def param_bytes(template: Any, dtype_bytes: int = 2) -> int:
    return param_count(template) * dtype_bytes
