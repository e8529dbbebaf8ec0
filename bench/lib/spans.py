"""The engine's own spans in a profiler trace, and what they tell.

``InstanceEngine`` annotates the host code between its jitted calls with
profiler spans (``PROGRAM_SPANS``); their arguments carry the counts at each
boundary and the request id.  ``load`` reads them from the ``.xplane.pb``
that ``trace.capture`` wrote, in seconds from the start of the
``bench.window`` span, the clock of ``trace.load``'s record of the same
directory.  The readers below reduce them, with that record's device ops, to
the engine's metrics:

    engine_queue_p90_ms          p90 of engine.prefill start - engine.enqueue, by rid
    host_syncs_per_token         sum of syncs / sum of tokens over engine.readback
    idle_frac.in_step.<group>    device-idle time inside engine.step whose
                                 innermost program span is in <group>, over
                                 the engine.step time (IN_STEP_GROUPS)

The four ``idle_frac.in_step.*`` shares add up to ``trace.idle_in_spans(t,
"engine.step")`` on one device (the first device is read, as
``trace.breakdown`` reads it).
"""

from __future__ import annotations

import bisect
import glob
import os

from bench.lib import trace as tr
from bench.lib.stats import percentile

PROGRAM_SPANS = ("engine.enqueue", "engine.admit", "engine.prefill", "engine.readback",
                 "engine.splice", "engine.decode", "engine.retire")
# innermost program span -> group; "" is time inside no program span
IN_STEP_GROUPS = {
    "readback": ("engine.readback",),
    "dispatch": ("engine.decode", "engine.prefill"),
    "update": ("engine.splice", "engine.retire"),
    "other": ("engine.admit", "engine.enqueue", ""),
}
STEP = "engine.step"

ProgramSpan = tuple[str, float, float, dict]  # name, start, end, args


def load(log_dir: str, names: tuple[str, ...] = PROGRAM_SPANS) -> list[ProgramSpan]:
    """The host spans named in ``names`` of the trace in ``log_dir``, sorted
    by start."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    raw, t0 = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    raw.append((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
                elif e.name == "bench.window" and t0 is None:
                    t0 = e.start_ns
    if t0 is None:
        raise ValueError("the trace holds no bench.window span")
    return sorted(((n, (s - t0) * 1e-9, (e - t0) * 1e-9, a) for n, s, e, a in raw),
                  key=lambda x: (x[1], -x[2]))


# -- readers ------------------------------------------------------------------


def queue_waits(program: list[ProgramSpan]) -> list[float]:
    """Seconds each request waited in the engine's queue: from its
    ``engine.enqueue`` to the start of its ``engine.prefill``."""
    enq = {a["rid"]: s for n, s, _, a in program if n == "engine.enqueue"}
    return [s - enq[a["rid"]] for n, s, _, a in program
            if n == "engine.prefill" and a["rid"] in enq]


def engine_queue_p90_ms(program: list[ProgramSpan]) -> float | None:
    waits = queue_waits(program)
    return percentile(waits, 90) * 1e3 if waits else None


def host_syncs_per_token(program: list[ProgramSpan]) -> float | None:
    syncs = sum(a["syncs"] for n, _, _, a in program if n == "engine.readback")
    tokens = sum(a["tokens"] for n, _, _, a in program if n == "engine.readback")
    return syncs / tokens if tokens else None


def innermost(program: list[ProgramSpan]) -> list[tuple[float, float, str]]:
    """Disjoint, sorted segments of time covered by program spans, each
    named by the innermost span over it (the spans of one thread nest)."""
    segs: list[tuple[float, float, str]] = []
    stack: list[tuple[str, float]] = []  # name, end
    cur = float("-inf")

    def emit(upto: float) -> None:
        nonlocal cur
        if stack and upto > cur:
            segs.append((cur, upto, stack[-1][0]))
        cur = max(cur, upto)

    for name, s, e, _ in sorted(program, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((name, e))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return segs


def idle_by_innermost(trace: tr.Trace, program: list[ProgramSpan],
                      span: str = STEP) -> tuple[dict[str, float], float] | None:
    """Device-idle seconds inside the host spans ``span`` (first device),
    keyed by the innermost program span over each idle instant ("" where
    none), and the seconds inside those spans."""
    steps = [(s, e) for n, s, e in trace.spans if n == span]
    total = sum(e - s for s, e in steps)
    if not steps or total <= 0 or not trace.devices:
        return None
    busy = tr.union(next(iter(trace.devices.values()))["ops"], steps[0][0], steps[-1][1])
    ends = [be for _, be in busy]
    idle = []
    for s, e in steps:
        cur, i = s, bisect.bisect_right(ends, s)  # the first busy interval ending after s
        while i < len(busy) and busy[i][0] < e:
            if busy[i][0] > cur:
                idle.append((cur, busy[i][0]))
            cur = max(cur, busy[i][1])
            i += 1
        if e > cur:
            idle.append((cur, e))
    segs = innermost(program)
    starts = [g[0] for g in segs]
    out: dict[str, float] = {}
    for a, b in idle:
        left = b - a
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segs) and segs[i][0] < b:
            ov = min(b, segs[i][1]) - max(a, segs[i][0])
            if ov > 0:
                out[segs[i][2]] = out.get(segs[i][2], 0.0) + ov
                left -= ov
            i += 1
        out[""] = out.get("", 0.0) + left
    return out, total


def longest_gaps(trace: tr.Trace, program: list[ProgramSpan], lo: float, hi: float,
                 top: int = 10) -> list[dict]:
    """The ``top`` longest device-idle gaps in [lo, hi] (first device), each
    with its place and its seconds under each host span and under each
    innermost engine span ("" where none)."""
    if not trace.devices:
        return []
    busy = tr.union(next(iter(trace.devices.values()))["ops"], lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s),
                  key=lambda g: g[0] - g[1])[:top]
    segs = innermost(program)
    out = []
    for s, e in gaps:
        host: dict[str, float] = {}
        for n, hs, he in trace.spans:
            ov = min(e, he) - max(s, hs)
            if n != "bench.window" and ov > 0:
                host[n] = host.get(n, 0.0) + ov
        engine = {"": e - s}
        for gs, ge, n in segs:
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                engine[n] = engine.get(n, 0.0) + ov
                engine[""] -= ov
        out.append({"start": s, "end": e, "host": host, "engine": engine})
    return out


def in_step_shares(trace: tr.Trace, program: list[ProgramSpan]) -> dict[str, float] | None:
    """``idle_frac.in_step`` split by ``IN_STEP_GROUPS``, in %."""
    r = idle_by_innermost(trace, program)
    if r is None:
        return None
    by, total = r
    return {g: 100.0 * sum(by.get(n, 0.0) for n in names) / total
            for g, names in IN_STEP_GROUPS.items()}


def metrics(trace: tr.Trace, program: list[ProgramSpan]) -> dict[str, float]:
    """The six engine metrics that have something to read."""
    out = {"engine_queue_p90_ms": engine_queue_p90_ms(program),
           "host_syncs_per_token": host_syncs_per_token(program)}
    for g, v in (in_step_shares(trace, program) or {}).items():
        out[f"idle_frac.in_step.{g}"] = v
    return {k: v for k, v in out.items() if v is not None}
