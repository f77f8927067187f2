"""95th percentile of every gap between consecutive output tokens of one
request, both inside the window."""
import numpy as np


def read(ctx):
    g = ctx.window.gaps()
    return float(np.percentile(g, 95)) if len(g) else None
