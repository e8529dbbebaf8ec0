"""90th percentile of time to first token, from each request's due time,
over every request due in the window (one with no token counts as
infinite, so failures cannot quiet the tail)."""

import math

from bench.lib.stats import percentile


def read(ctx):
    vals = [(r.stamps[0] - r.due) * 1e3 if r.stamps else math.inf for r in ctx.records]
    vals += [math.inf] * (ctx.attempted - len(ctx.records))
    v = percentile(vals, 90)
    return v if math.isfinite(v) else None
