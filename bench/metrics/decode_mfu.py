"""Model step: the decode steps' share of the chip's bf16 peak over the
traced window: model FLOPs of every decode step dispatched there (the
benchmark's count from the configuration and the live lengths: every
layer and the output head for one token a sequence, attention over its
context) over the window's length times the peak, %. It bounds what a
faster kernel inside the step can give."""


def read(ctx):
    t, p = ctx.trace, ctx.prof
    if t is None or p is None or not p.ok or not p.step_flops \
            or ctx.peaks is None:
        return None
    return 100.0 * p.step_flops / (t.window_s * ctx.peaks["bf16_flops_per_s"])
