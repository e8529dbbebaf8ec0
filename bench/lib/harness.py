"""One run of one cell: set-up, the measured window, the check, the result.

``run`` returns the result object that ``bench/run.py`` prints; the tests
call it with ``require_tpu=False`` and a tiny cell.
"""

from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

from bench.lib import check, client, spec, stats, traffic
from bench.lib import trace as tr
from bench.reference.common import Reference

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"  # also cache loads
WARM_RID = 1 << 30  # request ids of the warm-up, apart from the traffic's


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


class Context:
    """What a metric reader sees of one run."""

    def __init__(self, cell, win: client.Window, attempted: int, setup_s: float, peak: dict,
                 trace: tr.Trace | None):
        self.config = cell.config
        self.flops = cell.flops()
        self.seconds = win.seconds
        self.setup_s = setup_s
        self.steps = win.steps
        self.window_steps = [s for s in win.steps if s.t0 < win.seconds]
        self.all_records = win.records
        self.records = [r for r in win.records if r.due < win.seconds]
        self.attempted = attempted
        self.peak_flops = float(peak["bf16_flops_per_s"])
        self.trace = trace
        end = max([s.t1 for s in self.window_steps], default=win.seconds)
        self.window_s = max(win.seconds, end)
        self.busy_s = tr.busy_seconds(trace, 0.0, self.window_s) if trace else None

    def trace_module(self, fragment: str) -> tuple[float, int]:
        return tr.module_seconds(self.trace, fragment)

    def trace_idle_in(self, span: str) -> float | None:
        return tr.idle_in_spans(self.trace, span)


class CompileCounter:
    def __init__(self):
        import jax

        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.on and event == COMPILE_EVENT:
            self.n += 1


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``:
    a fixed path, so the first run of a cell compiles and the rest load."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(spec.ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_info(chips: int, require_tpu: bool) -> tuple[dict, object]:
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"no TPU found: JAX reports platform {d.platform!r} ({d.device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
    return info, d


def peaks_for(kind: str, bench_dir: str) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def warm(server, lengths: list[int], vocab: int, n_slots: int) -> None:
    """Every prompt length the cell's traffic can send, and enough requests
    at once that every slot is admitted, decodes and is freed."""
    n = max(len(lengths), n_slots)
    reqs = [traffic.Request(WARM_RID + i, lengths[i % len(lengths)], 2, 0.0) for i in range(n)]
    client.run_window(server, reqs, seconds=0.0, vocab=vocab, seed=0, drain_s=600.0)


def run(workload: str, seed: int, seconds: float, trace: bool, *, require_tpu: bool = True,
        root: str = spec.ROOT, bench_dir: str | None = None, t_start: float | None = None,
        server_hook=None) -> dict:
    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.load_cell(workload, root=root, bench_dir=bench_dir)
    import jax

    dev_info, dev = device_info(cell.chips, require_tpu)
    peak = peaks_for(dev_info["kind"], cell.bench_dir)
    log(f"device: platform {dev_info['platform']}, kind {dev_info['kind']}, count {dev_info['count']}")
    if require_tpu:
        log(f"compile cache: {compile_cache_dir()}")
    counter = CompileCounter()
    c = cell.config
    vocab = int(c["vocab_size"])
    mix = cell.traffic
    reqs = traffic.generate(mix, cell.cell, seconds)
    server = cell.driver().Server(cell, seed)
    if server_hook:
        server_hook(server)
    lengths = traffic.used_prompt_lengths(mix, cell.cell, seconds)
    warm(server, lengths, vocab, server.n_slots)
    log(f"warmed prompt lengths {lengths} on {server.n_slots} slots")

    clients = int(mix["clients"]) if mix["arrivals"] == "closed" else None
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    with tr.capture(trace_dir) if trace else contextlib.nullcontext():
        setup_s = time.perf_counter() - t_start
        counter.on = True
        with (jax.profiler.TraceAnnotation("bench.window") if trace else contextlib.nullcontext()):
            win = client.run_window(server, reqs, seconds=seconds, vocab=vocab, seed=seed,
                                    clients=clients, annotate=trace)
        counter.on = False
    attempted = len(reqs) if clients is None else sum(1 for r in win.records if r.due < seconds)
    finished = sum(1 for r in win.records if r.due < seconds and r.done)
    failed = attempted - finished
    stats_mem = dev.memory_stats() or {}
    mem_peak = int(stats_mem.get("peak_bytes_in_use", 0))
    late = stats.percentile(win.lateness, 90) if win.lateness else 0.0
    log(f"window {seconds:.0f} s: {attempted} due, {finished} finished, {len(win.steps)} steps, "
        f"{counter.n} compiles inside, generator lateness p90 {late * 1e3:.1f} ms, "
        f"drained at {win.drained_at:.1f} s")
    log(f"peak HBM {mem_peak} bytes (limit {stats_mem.get('bytes_limit')})")

    trace_rec = None
    if trace:
        t0 = time.perf_counter()
        trace_rec = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t0:.1f} s")

    ctx = Context(cell, win, attempted, setup_s, peak, trace_rec)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = cell.metric_reader(m["name"]).read(ctx)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the check: program state freed first, so the reference sets no peak
    records = [r for r in win.records if r.due < seconds]
    ck = cell.cell["check"]
    recs = check.sample(records, seed, int(ck["min_tokens"]), int(ck["max_requests"]))
    served = [list(server.tokens(r.handle)) for r in recs]
    server.close()
    del server
    gc.collect()
    t0 = time.perf_counter()
    g = check.served_gaps(Reference(c, cell.reference()), seed, recs, served, vocab)
    value = check.widest_gap(g)
    limit = float(ck["limit"])
    correct = check.verdict(value, failed, limit)
    log(f"check: {len(recs)} requests, {g.size} served tokens, reference {time.perf_counter() - t0:.1f} s")

    device = dict(dev_info, memory_peak_bytes=mem_peak)
    result = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        result["breakdown"] = tr.breakdown(trace_rec, 0.0, ctx.window_s)
    result["window_compiles"] = counter.n
    result["checks"] = {
        "widest_gap": {"value": value, "limit": limit},
        "unfinished": {"value": failed, "limit": 0},
    }
    for k, v in result["checks"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return result
