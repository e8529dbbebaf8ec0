"""Share of the chip's bf16 peak reached by the decode program while it
runs: FLOPs of the live slots at their context lengths (bench/flops) over
the device time of the ``_decode_all`` program runs in the trace.  None
when the trace's runs do not match the decode steps the client counted."""

PROGRAM = "_decode_all"


def read(ctx):
    if ctx.trace is None:
        return None
    steps = [s for s in ctx.steps if s.decode_lens]
    t, n = ctx.trace_module(PROGRAM)
    if not steps or n != len(steps) or t <= 0:
        return None
    return 100.0 * sum(ctx.flops.decode(ctx.config, s.decode_lens) for s in steps) / (t * ctx.peak_flops)
