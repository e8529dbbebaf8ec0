"""Share of the time inside the engine's step() calls (host spans
``engine.step``) in which no operation ran on the device."""


def read(ctx):
    if ctx.trace is None:
        return None
    v = ctx.trace_idle_in("engine.step")
    return None if v is None else 100.0 * v
