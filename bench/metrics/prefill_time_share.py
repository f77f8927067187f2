"""Model step: host time of the prefill phases (dispatch, harvest or
chunk, each waiting for its prefill to land) over the window, from the
engine's phase counter, %."""


def read(ctx):
    if "prefill_ns" not in ctx.counters_close or ctx.close <= 0:
        return None
    return 100.0 * ctx.delta("prefill_ns") / 1e9 / ctx.close
