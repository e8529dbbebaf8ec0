"""Share of the chip's bf16 peak reached by the prefill program while it
runs: the prefills' FLOPs (bench/flops, one unembed row each) over the
device time of the ``_prefill_one`` program runs in the trace.  None when
the trace's runs do not match the prefills the client counted."""

PROGRAM = "_prefill_one"


def read(ctx):
    if ctx.trace is None:
        return None
    lens = [n for s in ctx.steps for n in s.prefill_lens]
    t, n = ctx.trace_module(PROGRAM)
    if not lens or n != len(lens) or t <= 0:
        return None
    return 100.0 * sum(ctx.flops.prefill(ctx.config, s) for s in lens) / (t * ctx.peak_flops)
