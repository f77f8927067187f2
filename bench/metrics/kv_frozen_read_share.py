"""KV cache: of the pages the window's decode steps read, the share read
as 4-bit codes, as the engine counts it at each decode dispatch, %. The
program's own count of what ``frozen_page_share`` counts from outside."""


def read(ctx):
    if "kv_pages_read" not in ctx.counters_close:
        return None
    pages = ctx.delta("kv_pages_read")
    if pages <= 0:
        return None
    return 100.0 * ctx.delta("kv_pages_read_frozen") / pages
