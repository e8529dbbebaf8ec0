"""Host time that one admission (prefill of one prompt and its splice into a
slot) adds to an engine step: the median step with exactly one admission
less the median decode-only step."""

from bench.lib.stats import median


def read(ctx):
    one = [s.t1 - s.t0 for s in ctx.window_steps if len(s.admitted) == 1 and s.decode_lens]
    plain = [s.t1 - s.t0 for s in ctx.window_steps if not s.admitted and s.decode_lens]
    if not one or not plain:
        return None
    return (median(one) - median(plain)) * 1e3
