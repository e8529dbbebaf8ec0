"""Seconds from the process's start to the window's: weights drawn from
the seed, the engine built, the cell's shapes warmed."""


def read(ctx):
    return ctx.setup_s
