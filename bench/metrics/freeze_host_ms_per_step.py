"""Page freeze: host time of the freeze flush (the solve's dispatch) and
install phases, from the engine's phase counters, per decode step in the
window, ms. ``freeze_ms_per_step`` is the same work's device time."""
KEYS = ("freeze_flush_ns", "freeze_install_ns")


def read(ctx):
    steps = ctx.delta("decode_steps")
    if steps <= 0 or not all(k in ctx.counters_close for k in KEYS):
        return None
    return sum(ctx.delta(k) for k in KEYS) / steps / 1e6
