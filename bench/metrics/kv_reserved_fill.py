"""KV cache: rows the live sequences hold over the rows of the pages
reserved for them (admission reserves a request's prompt and its whole
output up front), summed over the window's decode steps, %."""


def read(ctx):
    if "kv_rows_reserved" not in ctx.counters_close:
        return None
    reserved = ctx.delta("kv_rows_reserved")
    if reserved <= 0:
        return None
    return 100.0 * ctx.delta("kv_rows_used") / reserved
