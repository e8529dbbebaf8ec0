"""90th percentile of the wait in the admission queue: from the request's
due time to the start of the engine step that admitted it."""

from bench.lib.stats import percentile


def read(ctx):
    vals = [(ctx.steps[r.admit_step].t0 - r.due) * 1e3 for r in ctx.records if r.admit_step >= 0]
    return percentile(vals, 90) if vals else None
