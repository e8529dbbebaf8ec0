"""Declarative simcheck configuration for THIS repo.

Everything the rules treat as policy lives here — scopes, the allowed
import edges, determinism allowlists, the sanctioned event-reaction APIs —
so a reviewer can audit the repo's invariants in one place without reading
rule implementations.  Tests inject custom configs to drive fixtures.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

__all__ = ["AnalysisConfig", "default_config", "ALLOWED_EDGES"]


# ---------------------------------------------------------------------------
# layering: the import DAG, as allowed edges
# ---------------------------------------------------------------------------
# Key = source package prefix (most specific match wins); value = target
# package prefixes modules under the key may import from ``repro``.  A
# module's own matched package is always allowed (intra-package imports).
# ``*`` = unconstrained (entrypoint layers).
#
# The constraints the repo's history made load-bearing:
#   * repro.net never imports repro.obs / repro.serving (PR 6 duck-typed
#     the tracer rather than add the edge);
#   * repro.obs never imports repro.serving or repro.core.simulator (the
#     observer must not depend on the observed);
#   * repro.core never imports repro.serving (PR 10 moved the trace
#     generators to repro.workloads to kill the last such edge);
#   * repro.workloads is the bottom: no repro imports at all;
#   * repro.kernels imports nothing from repro: its pure-jnp attention and
#     RMSNorm (repro.kernels.ref) are what repro.models runs and what each
#     Pallas kernel is tested against, so the models can call the kernels
#     without a cycle.
ALLOWED_EDGES: dict[str, tuple[str, ...]] = {
    "repro.workloads": (),
    "repro.distributed": (),
    "repro.data": (),
    "repro.analysis": (),
    "repro.models": ("repro.distributed", "repro.kernels"),
    "repro.configs": ("repro.models", "repro.distributed"),
    "repro.kernels": (),
    "repro.training": ("repro.models", "repro.distributed"),
    "repro.net": ("repro.core.topology", "repro.core.multicast"),
    "repro.obs": ("repro.net", "repro.workloads"),
    "repro.core": (
        "repro.net",
        "repro.obs",
        "repro.models",
        "repro.kernels",
        "repro.configs",
        "repro.workloads",
        "repro.distributed",
    ),
    "repro.serving": (
        "repro.core",
        "repro.net",
        "repro.obs",
        "repro.models",
        "repro.configs",
        "repro.workloads",
        "repro.distributed",
    ),
    # entrypoints: may import anything
    "repro.launch": ("*",),
}


@dataclasses.dataclass
class AnalysisConfig:
    # -- determinism ---------------------------------------------------------
    #: packages whose code must be wall-clock- and global-RNG-free
    determinism_scopes: tuple[str, ...] = (
        "repro.net",
        "repro.core",
        "repro.obs",
        "repro.serving",
    )
    #: module -> justification.  These measure REAL planning time as
    #: metadata (never simulation time), mirroring the paper's reported
    #: plan-generation costs.
    determinism_allowlist: Mapping[str, str] = dataclasses.field(
        default_factory=lambda: {
            "repro.core.multicast": "planner wall-clock gen_seconds metadata "
            "(Algorithm-11 generation cost, not simulation time)",
            "repro.core.zigzag": "ILP plan-generation wall-clock ms metadata",
        }
    )
    #: call prefixes that are wall-clock reads
    wall_clock_calls: tuple[str, ...] = (
        "time.time",
        "time.time_ns",
        "time.perf_counter",
        "time.perf_counter_ns",
        "time.monotonic",
        "time.monotonic_ns",
        "time.process_time",
        "time.process_time_ns",
        "datetime.datetime.now",
        "datetime.datetime.utcnow",
        "datetime.datetime.today",
        "datetime.date.today",
    )
    #: np.random constructors that are fine WHEN given an explicit seed
    seeded_rng_constructors: tuple[str, ...] = (
        "numpy.random.default_rng",
        "numpy.random.Generator",
        "numpy.random.SeedSequence",
        "numpy.random.PCG64",
        "numpy.random.Philox",
        "numpy.random.MT19937",
        "random.Random",
    )

    # -- set-iteration -------------------------------------------------------
    #: packages where event ordering is fed by iteration order
    iteration_scopes: tuple[str, ...] = ("repro.net", "repro.core.simulator")
    #: order-insensitive consumers: a set used as the sole iterable of
    #: these calls cannot leak ordering.  ``sum`` is deliberately NOT here:
    #: float addition is non-associative, so summing a set of floats in
    #: hash order is exactly the replay hazard this rule exists to catch.
    order_insensitive_calls: frozenset[str] = frozenset(
        {"sorted", "len", "min", "max", "any", "all", "set", "frozenset"}
    )
    #: calls that preserve their argument's (arbitrary) iteration order
    order_passthrough_calls: frozenset[str] = frozenset({"list", "tuple", "iter"})
    #: reducers whose result depends on consumption order even without a
    #: visible loop (non-associative float accumulation)
    order_sensitive_reducers: frozenset[str] = frozenset({"sum"})

    # -- layering ------------------------------------------------------------
    allowed_edges: Mapping[str, Sequence[str]] = dataclasses.field(
        default_factory=lambda: dict(ALLOWED_EDGES)
    )

    # -- exact-float ---------------------------------------------------------
    float_eq_scopes: tuple[str, ...] = ("repro.net",)
    #: epsilon helpers whose *call sites* establish sanctioned tolerance
    #: comparisons (==/!= touching their results is still flagged — the
    #: helpers are used with <=, never ==)
    float_eq_helpers: tuple[str, ...] = ("flow_done_eps",)

    # -- event-reentrancy ----------------------------------------------------
    #: method name registering a callback on the engine
    subscribe_method: str = "subscribe"
    #: engine internals a subscription callback must never reach: capacity
    #: mutations re-enter the full solve and re-emit events; underscore
    #: internals assume the settle loop's intermediate state
    reentrancy_forbidden: frozenset[str] = frozenset(
        {
            "_evict_failed",
            "_recompute",
            "_recompute_component",
            "_settle",
            "_set_path",
            "_cal_push",
            "_cal_pop",
            "_emit",
            "fail_link",
            "fail_device",
            "fail_leaf",
            "degrade_link",
            "recover_link",
            "recover_device",
        }
    )
    #: sanctioned reaction APIs — safe re-entry points the engine defines
    #: for use INSIDE an event.  The reachability walk treats them as
    #: opaque: calls *through* them are the supported contract.
    reentrancy_sanctioned: frozenset[str] = frozenset(
        {
            # FlowSim's in-event surface: starting/removing flows during a
            # failure event is the designed reaction path (aborts have
            # settled by emission time); estimates never mutate
            "start",
            "start_many",
            "remove",
            "estimate_transfer_time",
            # multicast execution wrappers over the same surface
            "launch",
            "cancel",
        }
    )

    # -- suffix match helpers ------------------------------------------------
    def in_scope(self, module: str, scopes: Sequence[str]) -> bool:
        return any(module == s or module.startswith(s + ".") for s in scopes)


def default_config() -> AnalysisConfig:
    return AnalysisConfig()
