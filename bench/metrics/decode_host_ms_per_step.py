"""Model step: host time of the decode steps' dispatch and commit phases
(the engine's own phase counters), per decode step in the window, ms. The
token sync between them, which waits for the chip, is left out."""
KEYS = ("decode_dispatch_ns", "decode_commit_ns")


def read(ctx):
    steps = ctx.delta("decode_steps")
    if steps <= 0 or not all(k in ctx.counters_close for k in KEYS):
        return None
    return sum(ctx.delta(k) for k in KEYS) / steps / 1e6
