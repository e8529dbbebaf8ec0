"""Median host time of a decode-only engine step (no admission in it)."""

from bench.lib.stats import median


def read(ctx):
    plain = [s.t1 - s.t0 for s in ctx.window_steps if not s.admitted and s.decode_lens]
    return median(plain) * 1e3 if plain else None
