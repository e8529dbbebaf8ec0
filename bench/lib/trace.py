"""Profiler trace -> a small plain record -> device time, idle share,
per-program time and the breakdown.

``capture`` runs the JAX profiler around the window; ``load`` reads the
``.xplane.pb`` it wrote into a ``Trace``: per device, the op events ("XLA
Ops" line) and the program events ("XLA Modules" line), and the host spans
that the client annotates (``SPANS``), all in seconds from the start of the
``bench.window`` span.  The same record is what the tests keep as a fixture.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re

SPANS = ("bench.window", "engine.step", "client.wait", "client.submit", "client.record")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")  # not CUSTOM:, not the host
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"

Interval = tuple[str, float, float]  # name, start, end


@dataclasses.dataclass
class Trace:
    devices: dict[str, dict[str, list[Interval]]]  # device -> {"ops", "modules"}
    spans: list[Interval]
    window: tuple[float, float]  # the bench.window span

    def to_json(self) -> dict:
        return {"devices": self.devices, "spans": self.spans, "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        devs = {k: {kk: [tuple(e) for e in vv] for kk, vv in v.items()} for k, v in d["devices"].items()}
        return cls(devs, [tuple(e) for e in d["spans"]], tuple(d["window"]))


@contextlib.contextmanager
def capture(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1  # annotations, not the runtime's own
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _short(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``;
    ``jit__decode_all(123)`` -> ``jit__decode_all``."""
    return name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


def _in_modules(ops: list, modules: list) -> list:
    """Prefix each op with the program it ran in: ``_decode_all:fusion.12``."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for n, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][0].removeprefix("jit_") if i >= 0 and mods[i][2] >= e else "?"
        out.append((f"{prog}:{n}", s, e))
    return out


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices: dict[str, dict[str, list]] = {}
    spans = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name.removeprefix("/device:"), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OP_LINE: "ops", MODULE_LINE: "modules"}.get(line.name)
                if key:
                    dev[key].extend((_short(e.name), e.start_ns, e.start_ns + e.duration_ns)
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events if e.name in SPANS)
    windows = [s for s in spans if s[0] == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    t0 = windows[0][1]

    def rel(evs):
        return sorted((n, (s - t0) * 1e-9, (e - t0) * 1e-9) for n, s, e in evs)

    devs = {}
    for d, lines in devices.items():
        mods = rel(lines["modules"])
        devs[d] = {"ops": _in_modules(rel(lines["ops"]), mods), "modules": mods}
    w = windows[0]
    return Trace(devs, rel(spans), (0.0, (w[2] - w[1]) * 1e-9))


# -- reduction ----------------------------------------------------------------


def union(intervals: list[Interval], lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged busy intervals, clipped to [lo, hi]."""
    out: list[list[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(busy: list[tuple[float, float]], lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in busy)


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which some op ran, averaged over the devices."""
    per = [covered(union(d["ops"], lo, hi), lo, hi) for d in trace.devices.values()]
    return sum(per) / len(per) if per else 0.0


def module_seconds(trace: Trace, fragment: str) -> tuple[float, int]:
    """Device seconds and count of the program runs whose name holds
    ``fragment`` (a jitted function's name), summed over the devices."""
    t, n = 0.0, 0
    for d in trace.devices.values():
        for name, s, e in d["modules"]:
            if fragment in name:
                t += e - s
                n += 1
    return t, n


def idle_in_spans(trace: Trace, span: str) -> float | None:
    """Share of the time inside host spans ``span`` with no op running."""
    spans = [(s, e) for n, s, e in trace.spans if n == span]
    total = sum(e - s for s, e in spans)
    if not spans or total <= 0 or not trace.devices:
        return None
    idle = []
    for d in trace.devices.values():
        busy = union(d["ops"], spans[0][0], spans[-1][1])
        idle.append(1.0 - sum(covered(busy, s, e) for s, e in spans) / total)
    return sum(idle) / len(idle)


def self_times(ops: list[Interval], lo: float, hi: float) -> list[tuple[str, float]]:
    """Each op's time in [lo, hi] less that of the ops nested in it (a
    ``while`` op spans the ops of its body)."""
    out = []
    stack: list[list] = []  # [name, start, end, children's time]

    def close(top):
        out.append((top[0], max(0.0, min(top[2], hi) - max(top[1], lo)) - top[3]))

    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack and e <= stack[-1][2]:
            stack[-1][3] += max(0.0, min(e, hi) - max(s, lo))
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def breakdown(trace: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """The device ops that took most time (self time, by program and op),
    and the longest idle gaps named by the host span they fell in (the
    first device)."""
    by_op: dict[str, float] = {}
    for d in trace.devices.values():
        for name, t in self_times(d["ops"], lo, hi):
            by_op[name] = by_op.get(name, 0.0) + t
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    if trace.devices:
        busy = union(next(iter(trace.devices.values()))["ops"], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        host = sorted(s for s in trace.spans if s[0] != "bench.window")
        starts = [s[1] for s in host]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e - s <= 0:
                continue
            best, label = 0.0, "host.other"
            i = bisect.bisect_right(starts, e) - 1
            while i >= 0 and host[i][2] > s - 60.0:  # spans last under a minute
                ov = min(e, host[i][2]) - max(s, host[i][1])
                if ov > best:
                    best, label = ov, host[i][0]
                i -= 1
            gaps.append((label, e - s))
        gaps.sort(key=lambda g: -g[1])
    return {"device_ops": [[n, t] for n, t in ops], "idle_gaps": [[n, t] for n, t in gaps[:top]]}
