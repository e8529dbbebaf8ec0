"""Live autoscaling: the cooperative execution protocol (paper §4, §5.2).

The scaling abstraction is broken from instance-level to *layer-level*:
while a scaling instance (the *target*) is still receiving parameters, it
executes the first ``k`` loaded layers of every request and forwards the
activation to the overloaded *source* instance, which finishes layers
``k..L``.  The pair's throughput rises from 1/L to 1/max(k, L-k) per
layer-time — 2x once half the layers have landed — so queued requests drain
*during* the transfer instead of after it.

Three-step transition protocol (paper Fig. 9d + §5.2):
  1. REDIRECT   — as loading starts, all queued + new requests are
                  redirected to the target's priority queue (cheap: request
                  payloads are tiny vs. parameters);
  2. COOPERATIVE— target executes loaded layers (ZigZag order), source pulls
                  and completes; throughput ramps with loaded layers;
  3. REBALANCE  — once all L layers landed, requests are split evenly and
                  both run as normal full instances.

``cooperative_forward`` is the *jittable* data-plane primitive: it computes
the exact same function as a monolithic forward (property-tested) while
splitting layer execution at a traced boundary ``k`` — i.e. per-``k``
recompilation is not needed when ``k`` advances during loading.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.zigzag import live_throughput_multiplier
from repro.kernels.ref import rmsnorm
from repro.models import layers as L
from repro.models import transformer as TF
from repro.models.config import ModelConfig


class Phase(enum.Enum):
    REDIRECT = "redirect"
    COOPERATIVE = "cooperative"
    REBALANCED = "rebalanced"


@dataclasses.dataclass
class LiveSession:
    """Host-side state machine coordinating one (source, target) pair.

    Progress is either constant-rate (``link_bytes_per_s``, the planner's
    dedicated-link estimate) or — when ``progress_bytes`` is set — read from
    the flow-level network simulator, so layer arrival reflects whatever
    contention the parameter stream actually experienced.  Callers using
    ``progress_bytes`` must advance their FlowSim to ``now`` before asking.
    """

    n_layers: int
    layer_bytes: int
    link_bytes_per_s: float
    started_at: float
    phase: Phase = Phase.REDIRECT
    # realized bytes delivered to the target (e.g. a FlowSim flow's
    # ``transferred``); overrides the constant-rate model when provided
    progress_bytes: Callable[[], float] | None = None

    def layers_loaded(self, now: float) -> int:
        if self.progress_bytes is not None:
            if self.layer_bytes <= 0:
                return self.n_layers
            return min(self.n_layers, int(self.progress_bytes() / self.layer_bytes))
        if self.link_bytes_per_s <= 0:
            return self.n_layers
        dt = max(0.0, now - self.started_at)
        return min(self.n_layers, int(dt * self.link_bytes_per_s / self.layer_bytes))

    def throughput_multiplier(self, now: float) -> float:
        k = self.layers_loaded(now)
        if k >= self.n_layers:
            self.phase = Phase.REBALANCED
            return 2.0
        if k >= 1 and self.phase is Phase.REDIRECT:
            self.phase = Phase.COOPERATIVE
        return live_throughput_multiplier(k, self.n_layers)

    def done_at(self) -> float:
        return self.started_at + self.n_layers * self.layer_bytes / self.link_bytes_per_s


# ---------------------------------------------------------------------------
# Jittable cooperative forward (layer-split execution)
# ---------------------------------------------------------------------------


def cooperative_forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # (B, S)
    k: jax.Array | int,  # layers loaded on the target (traced)
    frames: jax.Array | None = None,
) -> jax.Array:
    """Target executes layers [0, k), source executes [k, L); returns logits.

    In the real deployment the two ranges run on different instances with an
    activation transfer between them; numerically the composition must equal
    the monolithic forward — that equality is the correctness contract
    (tested in tests/test_live_scaling.py).
    """
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = TF._embed(cfg, params, tokens, frames)
    shared = params.get("shared")
    # ---- target side: layers [0, k)
    x = TF.forward_layers_range(cfg, params["layers"], x, 0, k, positions, shared)
    # (activation crosses the network here)
    # ---- source side: layers [k, L)
    x = TF.forward_layers_range(
        cfg, params["layers"], x, k, cfg.n_layers, positions, shared
    )
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(params["embed"], x, cfg)


def select_live_pairs(
    plan,  # MulticastPlan
    overloaded: list[int],  # device ids of overloaded instances
    *,
    slo_requires_live: bool = True,
) -> list[tuple[int, int]]:
    """§5.2 'Selecting instances for live scaling': pair each overloaded
    instance with a chain-tail node (slowest link, free egress — Fig. 12).
    Returns (source_device, target_device) pairs."""
    if not slo_requires_live:
        return []
    tails = [n.device_ids[0] for n in plan.live_scale_nodes]
    return list(zip(overloaded, tails))
