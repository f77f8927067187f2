"""Page freeze: device time of the freeze programs (the batched page
solve and its install) in the traced window, per decode step there."""
PROGRAMS = r"_solve_leaf_pages|_install_leaf"


def read(ctx):
    t, p = ctx.trace, ctx.prof
    if t is None or p is None or not p.steps:
        return None
    return 1e3 * t.module_time(PROGRAMS) / p.steps
