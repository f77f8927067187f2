"""Scheduler: 95th percentile of prefill start minus due time, over the
window's attempted requests (censored at the close)."""
import numpy as np


def read(ctx):
    xs = ctx.window.censored("prefill_start")
    return float(np.percentile(xs, 95)) if xs else None
