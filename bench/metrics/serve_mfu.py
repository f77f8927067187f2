"""Model step: model FLOPs of every prompt and output token processed in
the window, as the benchmark counts them from the configuration (matmul
weights, causal attention over the context, the output head for sampled
rows), over the window times the chip's bf16 peak, %."""
from benchkit import costs


def read(ctx):
    if ctx.peaks is None:
        return None
    m, w = ctx.dims, ctx.window
    flops = 0
    for r, times in zip(w.records, w.token_times()):
        if r.prefill_start is not None and r.prefill_start <= w.close:
            flops += costs.prefill_flops(m, r.prompt_len)
        if len(times) > 1:
            flops += costs.decode_flops(m, r.prompt_len, len(times) - 1)
    if not flops:
        return None
    return 100.0 * flops / (ctx.close * ctx.peaks["bf16_flops_per_s"])
