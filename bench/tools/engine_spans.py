"""One traced run of a cell, with the engine's own spans read as well.

    python3 bench/tools/engine_spans.py --workload granite-8b.code --seed 7 --seconds 51

Runs ``harness.run`` exactly as ``bench/run.py --trace 1`` does and also
reads, from the same profiler trace, the spans ``InstanceEngine`` emits
(``bench/lib/spans.py``): the queue wait inside the engine, host syncs per
token, the device-idle time inside ``engine.step`` split by the innermost
engine span, and the trace's longest idle gaps with the host and engine
spans they fall in (Python's garbage collections are recorded as spans
``python.gc`` for this).  Prints the in-step table and the gaps on standard
error, and one JSON line last on standard output: the run's result with an
``engine_spans`` object added.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from jax.profiler import TraceAnnotation  # noqa: E402

from bench.lib import harness, spans  # noqa: E402
from bench.lib import trace as tr  # noqa: E402


GC_SPAN = "python.gc"


def run(workload: str, seed: int, seconds: float, **harness_kw) -> dict:
    """``harness.run`` with ``trace`` on, its result given ``engine_spans``."""
    read: dict = {}
    load_trace = tr.load

    def load_both(log_dir: str) -> tr.Trace:
        # the harness removes the trace directory once it has read it
        read["trace"] = t = load_trace(log_dir)
        read["spans"] = spans.load(log_dir, spans.PROGRAM_SPANS + (GC_SPAN,))
        return t

    collecting: list = []

    def gc_span(phase: str, info: dict) -> None:
        # each collection of Python's garbage collector as a host span
        if phase == "start":
            collecting.append(TraceAnnotation(GC_SPAN, generation=info["generation"]))
            collecting[-1].__enter__()
        elif collecting:
            collecting.pop().__exit__(None, None, None)

    tr.load = load_both
    gc.callbacks.append(gc_span)
    try:
        result = harness.run(workload, seed, seconds, True, **harness_kw)
    finally:
        gc.callbacks.remove(gc_span)
        tr.load = load_trace
    trace = read["trace"]
    program = [p for p in read["spans"] if p[0] != GC_SPAN]
    by, step_s = spans.idle_by_innermost(trace, program) or ({}, 0.0)
    if step_s:
        harness.log(f"in-step idle by innermost span, of {step_s:.3f} s inside engine.step:")
        for name, secs in sorted(by.items(), key=lambda kv: -kv[1]):
            harness.log(f"  {name or '(none)':16s} {secs:9.4f} s  {100.0 * secs / step_s:7.3f}%")
    gaps = spans.longest_gaps(trace, read["spans"], *trace.window)  # the drain too
    for g in gaps:
        harness.log(f"idle gap {g['start']:.3f}-{g['end']:.3f} s: host {g['host']}, "
                    f"engine {g['engine']}")
    counts: dict[str, int] = {}
    for name, *_ in program:
        counts[name] = counts.get(name, 0) + 1
    live = [a["live"] for n, _, _, a in program if n == "engine.decode"]
    idle = tr.idle_in_spans(trace, spans.STEP)
    result["engine_spans"] = {
        "metrics": spans.metrics(trace, program),
        "idle_frac.in_step": None if idle is None else 100.0 * idle,
        "in_step_idle_s": by,
        "step_s": step_s,
        "span_counts": counts,
        "host_syncs": sum(a["syncs"] for n, _, _, a in program if n == "engine.readback"),
        "decode_live_mean": sum(live) / len(live) if live else None,
        "longest_gaps": gaps,
    }
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args()
    try:
        result = run(a.workload, a.seed, a.seconds, t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
