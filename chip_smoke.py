"""Bring-up smoke run: the served path end to end on one TPU chip.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chips  # the multicast data plane on four chips

One chip: builds granite-8b at its published widths, cut to the 18 layers
of one pipeline stage (``ONE_CHIP`` in ``src/repro/configs/granite_8b.py``),
with random weights drawn from ``--seed``, and serves a burst of requests
through the normal entry points of ``repro.launch.serve``: the colocated
path (one engine serving while a second live-scales in) and the
PD-disaggregated runtime (``--disagg``).  It checks that every request got
all its tokens, that the runtime dropped or token-gapped none, and that
one request from each path agrees with a cache-free forward pass of the
same model (see ``check_logits``).

Four chips: ``core.collectives.chain_broadcast`` moves one granite layer's
parameters from chip 0 along chips 0->1->2->3; every chip's copy is compared
bit for bit with the source.  The plain reference is a ``jax.device_put``
of the same bytes to each chip.  Both times are printed as information.

Earlier lines print the device, the compile seconds of each phase, the
compile-cache directory and the peak HBM in use.  The last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.  There
is no CPU branch: without a TPU the script exits non-zero and prints no
result.  It runs in one process and starts no other.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.collectives import chain_broadcast  # noqa: E402
from repro.distributed.sharding import init_from_template  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer as TF  # noqa: E402

ARCH = "granite-8b"
# a logit within this many standard deviations of its row's logits of the
# row maximum passes (see check_logits for the derivation)
LOGIT_TOL_SIGMA = 0.35
# the engine caches plus the parameters must leave this share of HBM free
HBM_HEADROOM = 0.10

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",  # includes cache loads
)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileMeter:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache) in each phase, from JAX's own monitoring events."""

    def __init__(self):
        self.phase: str | None = None
        self.seconds: dict[str, float] = {}
        self.cache_hits: dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if self.phase is not None and event in COMPILE_EVENTS:
            self.seconds[self.phase] = self.seconds.get(self.phase, 0.0) + duration

    def _on_event(self, event: str, **_) -> None:
        if self.phase is not None and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits[self.phase] = self.cache_hits.get(self.phase, 0) + 1

    def start(self, phase: str) -> float:
        self.phase = phase
        return time.perf_counter()

    def end(self, t0: float) -> None:
        phase, self.phase = self.phase, None
        log(
            f"phase {phase}: {time.perf_counter() - t0:.1f}s wall, "
            f"compile {self.seconds.get(phase, 0.0):.1f}s "
            f"({self.cache_hits.get(phase, 0)} persistent-cache hits)"
        )


def report_hbm(dev) -> None:
    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    if peak is None or limit is None:
        raise SystemExit(f"FAIL: {dev.device_kind} reports no memory_stats()")
    log(f"peak_bytes_in_use {peak} of bytes_limit {limit} ({peak / limit:.1%})")
    if peak > (1.0 - HBM_HEADROOM) * limit:
        raise SystemExit(f"FAIL: peak HBM {peak} within {HBM_HEADROOM:.0%} of the limit")


def plan_hbm(cfg, args, dev) -> None:
    """Parameters plus the caches of every engine the topology can hold
    (the runtime provisions at most one engine per device of its 2x4
    cluster; the colocated path builds two)."""
    param_bytes = cfg.approx_params() * jnp.dtype(cfg.dtype).itemsize
    max_seq = args.prompt_len + args.gen_len + 8
    cache_bytes = (
        cfg.n_layers * 2 * args.n_slots * max_seq * cfg.n_kv_heads
        * cfg.resolved_head_dim * jnp.dtype(cfg.dtype).itemsize
    )
    n_engines = 8
    planned = param_bytes + n_engines * cache_bytes
    limit = (dev.memory_stats() or {}).get("bytes_limit", 0)
    log(
        f"HBM plan: parameters {param_bytes} + {n_engines} engines x cache "
        f"{cache_bytes} (n_slots {args.n_slots}, max_seq {max_seq}) = {planned} "
        f"of bytes_limit {limit}"
    )
    if planned > (1.0 - HBM_HEADROOM) * limit:
        raise SystemExit("FAIL: the planned engines do not fit one chip")


def check_logits(cfg, params, req, forward, label: str) -> None:
    """One request through the cache against a cache-free pass.

    ``TF.train_forward`` runs over the prompt plus the generated tokens.  At
    every generated position the logit of the token the engine chose must
    lie within ``LOGIT_TOL_SIGMA`` standard deviations (of that row's
    logits) of the row's maximum.  Logits are compared, not tokens: with
    random weights the top two logits are often closer than rounding.

    Why 0.35: both passes keep activations in bf16 (unit roundoff 2^-8).
    Rounding at the 2 x 18 residual additions adds up like a random walk
    to a relative error of sqrt(36) x 2^-8 = 2.3% of the hidden state, and
    a logit, a 4096-term dot product with it, carries the same relative
    error: 0.023 sigma of its row.  The gap between the chosen and the
    maximal logit mixes four such errors (two logits, two passes), so its
    standard deviation is 2 x 0.023 = 0.047 sigma; six of those are 0.28
    sigma.  The bf16 rounding of the logits themselves adds one ulp at the
    row maximum (about 4 sigma): 2^-7 x 4 = 0.03 sigma per logit.  Together
    0.34, rounded up to 0.35.  A token drawn at random sits about 4 sigma
    below the maximum of 49152 logits, so the check does reject it.
    """
    s, g = len(req.prompt), len(req.out_tokens)
    if g != req.max_new_tokens:
        raise SystemExit(f"FAIL: rid {req.rid} has {g} of {req.max_new_tokens} tokens")
    seq = np.concatenate([req.prompt, np.asarray(req.out_tokens[:-1], np.int32)])
    logits, _ = forward(params, jnp.asarray(seq[None]))
    rows = np.asarray(logits[0, s - 1 :, : cfg.vocab_size].astype(jnp.float32))  # (g, V)
    chosen = rows[np.arange(g), np.asarray(req.out_tokens)]
    top, sigma = rows.max(axis=1), rows.std(axis=1)
    gap = (top - chosen) / sigma
    spread = (top - np.median(rows, axis=1)) / sigma
    n_argmax = int((rows.argmax(axis=1) == np.asarray(req.out_tokens)).sum())
    log(
        f"logit check ({label}, rid {req.rid}, prompt {s}, generated {g}): "
        f"worst gap {gap.max():.4f} sigma (tolerance {LOGIT_TOL_SIGMA}), "
        f"argmax agrees at {n_argmax}/{g} positions, "
        f"max-to-median spread {np.median(spread):.2f} sigma"
    )
    if not np.isfinite(rows).all():
        raise SystemExit("FAIL: non-finite logits in the cache-free pass")
    if gap.max() > LOGIT_TOL_SIGMA:
        raise SystemExit(f"FAIL: logit check ({label}) off by {gap.max():.4f} sigma")
    log(f"logit check ({label}) passed")


def serve_args(*extra: str) -> argparse.Namespace:
    return serve.build_parser().parse_args([
        "--arch", ARCH, "--one-chip", "--requests", "8", "--prompt-len", "512",
        "--gen-len", "32", "--n-slots", "4", *extra,
    ])


def run_one_chip(seed: int, meter: CompileMeter, dev) -> None:
    args = serve_args("--seed", str(seed))

    t0 = meter.start("init")
    cfg, params = serve.load_model(args)
    jax.block_until_ready(params)
    log(
        f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}, {cfg.approx_params()} parameters (seed {seed})"
    )
    plan_hbm(cfg, args, dev)
    meter.end(t0)
    report_hbm(dev)

    t0 = meter.start("colocated")
    finished = serve.run_colocated(args, cfg, params)
    meter.end(t0)
    short = [r.rid for r in finished if len(r.out_tokens) != args.gen_len]
    if len(finished) != args.requests or short:
        raise SystemExit(f"FAIL: colocated served {len(finished)}, short {short}")
    log(f"colocated: {len(finished)}/{args.requests} requests served, "
        f"{args.gen_len} tokens each")
    report_hbm(dev)

    t0 = meter.start("disagg")
    rt = serve.run_disagg(serve_args("--seed", str(seed), "--disagg"), cfg, params)
    meter.end(t0)
    done = list(rt.completed.values())
    short = [r.rid for r in done if len(r.out_tokens) != args.gen_len]
    if len(done) != args.requests or short or rt.n_outstanding:
        raise SystemExit(f"FAIL: disagg served {len(done)}, short {short}")
    _, gapped = rt.router.handoff_report()
    log(f"disagg: {len(done)}/{args.requests} requests served, "
        f"{rt.n_outstanding + gapped} dropped or token-gapped")
    report_hbm(dev)

    t0 = meter.start("logit_check")
    forward = jax.jit(lambda p, t: TF.train_forward(cfg, p, t))
    check_logits(cfg, params, finished[0], forward, "colocated")
    check_logits(cfg, params, min(done, key=lambda r: r.rid), forward, "disagg")
    meter.end(t0)
    report_hbm(dev)


def run_four_chips(seed: int, meter: CompileMeter) -> None:
    """chain_broadcast of one granite layer 0->1->2->3 against device_put."""
    devs = jax.devices()[:4]
    if len(devs) < 4:
        raise SystemExit(f"FAIL: --four-chips needs 4 devices, found {len(devs)}")
    mesh = jax.sharding.Mesh(np.array(devs), ("chain",))
    cfg = get_config(ARCH, one_chip=True)

    t0 = meter.start("layer_init")
    with jax.default_device(devs[0]):
        layer = init_from_template(jax.random.PRNGKey(seed), TF.layer_template(cfg))
        src = jnp.concatenate([x.ravel() for x in jax.tree.leaves(layer)])
    src.block_until_ready()
    del layer
    nbytes = src.size * src.dtype.itemsize
    log(f"one {cfg.name} layer: {src.size} {src.dtype} elements, {nbytes} bytes on {devs[0]}")
    want = np.asarray(src).view(np.uint16)
    meter.end(t0)

    t0 = meter.start("chain_broadcast")
    chain_broadcast(src, mesh, "chain").block_until_ready()  # compile + warm up
    t = time.perf_counter()
    out = chain_broadcast(src, mesh, "chain")
    out.block_until_ready()
    chain_s = time.perf_counter() - t
    meter.end(t0)
    for shard in sorted(out.addressable_shards, key=lambda s: s.index[0].start):
        rank = shard.index[0].start
        if shard.device != devs[rank]:
            raise SystemExit(f"FAIL: rank {rank} copy is on {shard.device}")
        same = np.array_equal(np.asarray(shard.data)[0].view(np.uint16), want)
        log(f"chain_broadcast: chip {rank} ({shard.device}) copy matches the source "
            f"bit for bit: {same}")
        if not same:
            raise SystemExit(f"FAIL: chip {rank} copy differs from the source")
    del out

    t0 = meter.start("device_put")
    for d in devs[1:]:
        jax.device_put(src, d).block_until_ready()  # warm up
    t = time.perf_counter()
    copies = [jax.device_put(src, d) for d in devs[1:]]
    jax.block_until_ready(copies)
    put_s = time.perf_counter() - t
    meter.end(t0)
    for d, c in zip(devs[1:], copies):
        if not np.array_equal(np.asarray(c).view(np.uint16), want):
            raise SystemExit(f"FAIL: device_put copy on {d} differs from the source")
    log("device_put reference: copies on chips 1-3 match the source bit for bit")
    log(f"chain_broadcast 0->1->2->3 of {nbytes} bytes: {chain_s:.6f} s "
        f"(information only)")
    log(f"device_put 0->{{1,2,3}} of {nbytes} bytes: {put_s:.6f} s (information only)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip multicast data plane and its reference")
    args = ap.parse_args()

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
                 "this script runs on the chip only")
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, count {len(devs)}")
    log(f"compile cache: {enable_compile_cache()}")
    meter = CompileMeter()
    if args.four_chips:
        run_four_chips(args.seed, meter)
    else:
        run_one_chip(args.seed, meter, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}}),
        flush=True)


if __name__ == "__main__":
    main()
