"""Tokens delivered inside the window over the window's seconds."""


def read(ctx):
    return sum(1 for r in ctx.all_records for t in r.stamps if t <= ctx.seconds) / ctx.seconds
