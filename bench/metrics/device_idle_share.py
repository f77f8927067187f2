"""Device: share of the traced window in which no operation ran on the
chip (1 - busy union / window), %."""


def read(ctx):
    t = ctx.trace
    return None if t is None else 100.0 * t.idle_share
