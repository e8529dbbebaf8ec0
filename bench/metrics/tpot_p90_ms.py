"""90th percentile over the window's finished requests of each request's
mean time per output token: (last token - first token) / (tokens - 1),
as the client saw them."""

from bench.lib.stats import percentile


def read(ctx):
    vals = [(r.stamps[-1] - r.stamps[0]) * 1e3 / (len(r.stamps) - 1)
            for r in ctx.records if r.done and len(r.stamps) > 1]
    return percentile(vals, 90) if vals else None
