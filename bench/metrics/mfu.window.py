"""The whole step's share of the chip's bf16 peak over the traced window:
the useful model FLOPs of every prefill and decode the window's steps ran,
over the window's seconds times the peak."""


def read(ctx):
    if ctx.trace is None:
        return None
    fl = sum(sum(ctx.flops.prefill(ctx.config, n) for n in s.prefill_lens)
             + (ctx.flops.decode(ctx.config, s.decode_lens) if s.decode_lens else 0)
             for s in ctx.window_steps)
    return 100.0 * fl / (ctx.window_s * ctx.peak_flops)
