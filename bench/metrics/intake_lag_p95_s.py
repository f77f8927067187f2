"""Engine run loop: 95th percentile of when the engine noticed a request
minus when it was due, over the window's attempted requests (censored at
the close)."""
import numpy as np


def read(ctx):
    xs = ctx.window.censored("arrival")
    return float(np.percentile(xs, 95)) if xs else None
