"""95th percentile, over every request due in the window, of first token
minus due time; a request with no first token at the close (or refused)
counts close minus due."""
import numpy as np


def read(ctx):
    xs = ctx.window.ttfts()
    return float(np.percentile(xs, 95)) if xs else None
