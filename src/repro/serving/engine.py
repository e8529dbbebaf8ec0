"""Per-instance serving engine: continuous batching over jitted JAX steps.

One :class:`InstanceEngine` is what runs on a serving instance (a TP group of
chips).  It owns the parameters, a slotted KV cache, and pre-lowered
executables — the TPU analogue of the paper's CUDA-context-pool trick
(App. A.1): the decode step compiles once per (arch, n_slots) and prefill
once per prompt-length bucket, so autoscaling never pays a compile at
scale time.

Continuous batching (Orca-style): a fixed number of decode slots; finished
sequences free their slot immediately and queued requests are admitted at
the next step boundary.  ``loaded_layers`` tracks live-scaling progress: a
partially-loaded engine reports ``can_serve_alone() == False`` and the live
execution scheduler routes its work through cooperative execution instead.

Observability: the host code between the jitted calls is annotated with
profiler spans (``engine.enqueue``, ``engine.admit``, ``engine.prefill``,
``engine.readback``, ``engine.splice``, ``engine.decode``,
``engine.retire``) whose arguments carry the counts at each boundary and
the request id (``engine.decode``: live slots and the context positions
they attend over); they cost one inactive TraceMe each unless a profiler
trace is running, and read no clock.  ``stats`` (:class:`EngineStats`)
counts the same events.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import transformer as TF
from repro.models.config import ModelConfig
from repro.obs.metrics import StatBlock


@dataclasses.dataclass
class ServeRequest:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    slot: int | None = None
    done: bool = False


@dataclasses.dataclass
class EngineStats(StatBlock):
    admitted: int = 0  # requests spliced into a decode slot
    decode_steps: int = 0  # ``_decode_all`` calls
    host_syncs: int = 0  # device->host transfers of sampled tokens: one per decode step and prefill
    tokens: int = 0  # tokens delivered to requests
    ctx_tokens: int = 0  # cache positions the decode steps attended over, live slots
    cache_bytes: int = 0  # bytes of the slot caches, set once at build


class InstanceEngine:
    """Continuous-batching engine around the unified model.

    Every decode step runs all ``n_slots`` slots.  A free slot's cache is
    scratch: its K/V, state and ``lengths`` keep changing (``lengths`` may
    pass ``max_seq``, where the append writes nothing) and nothing reads
    them, until ``_splice_slot`` rewrites every leaf with a slot axis for
    the whole slot on the next admission."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        *,
        n_slots: int = 8,
        max_seq: int = 512,
    ):
        # per-row (non-lockstep) appends: engine slots are admitted at
        # different times, so their cache positions differ (§Perf C2 note)
        self.cfg = cfg = cfg.replace(uniform_decode=False)
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.queue: deque[ServeRequest] = deque()
        self.active: dict[int, ServeRequest] = {}  # slot -> request
        self.free_slots = list(range(n_slots))[::-1]
        self.caches = TF.init_caches(cfg, n_slots, max_seq)
        self.last_tokens = jnp.zeros((n_slots,), jnp.int32)
        self.slot_live = jnp.zeros((n_slots,), bool)
        self.loaded_layers = cfg.n_layers  # < n_layers while live-scaling
        self.stats = EngineStats(
            cache_bytes=sum(x.nbytes for x in jax.tree.leaves(self.caches)))

        n = self.n_slots

        @jax.jit
        def _decode_all(params, last_tokens, caches, live_mask):
            nxt, new_caches = TF.decode_step(cfg, params, last_tokens, caches)
            return jnp.where(live_mask, nxt, last_tokens), new_caches

        @jax.jit
        def _prefill_one(params, tokens):
            one = TF.init_caches(cfg, 1, max_seq)
            return TF.prefill(cfg, params, tokens, one)

        self._decode_all = _decode_all
        self._prefill_one = _prefill_one
        # eager updates per splice: the cache leaves with a slot axis, plus
        # last_tokens and slot_live
        self._splice_ops = 2 + sum(
            1 for x in jax.tree.leaves(self.caches) if x.ndim >= 2 and x.shape[1] == n
        )

    # -- live scaling hooks -----------------------------------------------------
    def set_loaded_layers(self, k: int) -> None:
        self.loaded_layers = min(k, self.cfg.n_layers)

    def can_serve_alone(self) -> bool:
        return self.loaded_layers >= self.cfg.n_layers

    # -- public API --------------------------------------------------------------
    def submit(self, req: ServeRequest) -> None:
        self.queue.append(req)
        with TraceAnnotation("engine.enqueue", rid=req.rid, depth=len(self.queue)):
            pass

    def _splice_slot(self, req: ServeRequest, one: Any, first_token: int) -> None:
        """Install a 1-slot prefill cache + its first sampled token into a
        free slot and make ``req`` live there.  Shared by local admission and
        disagg KV-migration admission so both paths are numerically
        identical."""
        slot = self.free_slots.pop()
        req.slot = slot

        def splice(old, new):
            if old.ndim >= 2 and old.shape[1] == self.n_slots:
                return old.at[:, slot].set(new[:, 0])
            return old

        with TraceAnnotation("engine.splice", rid=req.rid, slot=slot, ops=self._splice_ops):
            self.caches = jax.tree.map(splice, self.caches, one)
            self.last_tokens = self.last_tokens.at[slot].set(int(first_token))
            self.slot_live = self.slot_live.at[slot].set(True)
        self.active[slot] = req
        self.stats.admitted += 1

    def _admit(self) -> None:
        if not self.queue:
            return
        admitted = 0
        with TraceAnnotation("engine.admit", queued=len(self.queue),
                             free=len(self.free_slots)) as span:
            while self.queue and self.free_slots:
                req = self.queue.popleft()
                nxt, one = self.prefill_only(req)
                self._splice_slot(req, one, nxt)
                admitted += 1
            span.set_metadata(admitted=admitted)

    # -- disaggregated-serving entry points --------------------------------------
    def prefill_only(self, req: ServeRequest) -> tuple[int, Any]:
        """Run the prefill phase only: returns (first_token, 1-slot cache).

        On a prefill instance this is the whole job — the returned cache is
        the KV-migration payload; the first token is emitted here (TTFT is a
        prefill-side metric in PD disaggregation)."""
        with TraceAnnotation("engine.prefill", rid=req.rid, prompt_len=len(req.prompt)):
            tokens = jnp.asarray(req.prompt[None].astype(np.int32))
            nxt, one = self._prefill_one(self.params, tokens)
            with TraceAnnotation("engine.readback", syncs=1, tokens=1):
                first = int(jax.device_get(nxt)[0])
        req.out_tokens.append(first)
        self.stats.host_syncs += 1
        self.stats.tokens += 1
        return first, one

    def admit_prefilled(self, req: ServeRequest, first_token: int, one: Any) -> bool:
        """Admit a request whose prefill ran elsewhere (KV cache migrated in).

        Returns False when no decode slot is free — the caller keeps the
        payload queued.  The splice is the same op local admission uses, so
        decode continues bit-identically from the migrated state."""
        if not self.free_slots:
            return False
        self._splice_slot(req, one, first_token)
        return True

    def kv_used_frac(self) -> float:
        """Fraction of KV capacity held by live sequences (autoscaler signal)."""
        used = sum(
            len(r.prompt) + len(r.out_tokens) for r in self.active.values()
        )
        return used / float(self.n_slots * self.max_seq)

    def step(self) -> list[ServeRequest]:
        """One continuous-batching iteration; returns finished requests.

        The decode runs every slot; free slots keep their last token and
        their caches are scratch until a splice rewrites them.  After the
        decode, one device-to-host transfer brings every slot's token to the
        host, whatever the number of live slots; the live slots' tokens are
        read from that copy and the slots whose requests are done are
        freed."""
        self._admit()
        finished: list[ServeRequest] = []
        if not self.active:
            return finished
        live = list(self.active.items())
        # positions each live slot attends over in this step: its prompt and
        # every token served, the last of which the decode appends
        ctx = sum(len(req.prompt) + len(req.out_tokens) for _, req in live)
        with TraceAnnotation("engine.decode", live=len(live), ctx_tokens=ctx):
            nxt, self.caches = self._decode_all(
                self.params, self.last_tokens, self.caches, self.slot_live
            )
        self.last_tokens = nxt
        self.stats.decode_steps += 1
        self.stats.ctx_tokens += ctx
        with TraceAnnotation("engine.readback", syncs=1, tokens=len(live)):
            toks = jax.device_get(nxt)
            for slot, req in live:
                req.out_tokens.append(int(toks[slot]))
        self.stats.host_syncs += 1
        self.stats.tokens += len(live)
        done = [(slot, req) for slot, req in live if len(req.out_tokens) >= req.max_new_tokens]
        if not done:
            return finished
        with TraceAnnotation("engine.retire", finished=len(done), ops=len(done)):
            for slot, req in done:
                req.done = True
                finished.append(req)
                self.active.pop(slot)
                self.free_slots.append(slot)
                self.slot_live = self.slot_live.at[slot].set(False)
        return finished

    def run_until_done(self, max_steps: int = 10_000) -> list[ServeRequest]:
        out: list[ServeRequest] = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.active and not self.queue:
                break
        return out
