"""Model step: median host time of the engine's decode steps in the
window (dispatch, device sync, token commit), taken by the harness
tracer."""


def read(ctx):
    return 1e3 * ctx.median(ctx.step_s) if ctx.step_s else None
