"""KV cache: of the pages the window's decode steps read, the share read
as 4-bit codes (frozen), from the engine's table, lengths and frozen set
at each step, %."""


def read(ctx):
    p = ctx.prof
    if p is None or not p.ok or not p.pages_read:
        return None
    return 100.0 * p.pages_frozen / p.pages_read
