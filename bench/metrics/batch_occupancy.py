"""Scheduler: live sequences per decode step over the slots, in the
window (deltas of the engine's seq_decode_steps and decode_steps), %."""


def read(ctx):
    steps = ctx.delta("decode_steps")
    if steps <= 0:
        return None
    return 100.0 * ctx.delta("seq_decode_steps") / steps / ctx.max_slots
