"""Kernels: the fused paged-decode attention kernel's share of its
roofline in the traced window: the least time its calls need (the larger
of bytes over HBM bandwidth and FLOPs over the bf16 peak, from the
benchmark's own count: frozen pages at packed 4-bit size plus codebooks,
other pages at bf16) over the kernel's device time, %."""
KERNEL = r"paged_decode"


def read(ctx):
    t, p = ctx.trace, ctx.prof
    if t is None or p is None or not p.ok or not p.steps or ctx.peaks is None:
        return None
    dev = t.op_time(KERNEL)
    if dev <= 0:
        return None
    need = max(p.bytes / ctx.peaks["hbm_bytes_per_s"],
               p.flops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * need / dev
