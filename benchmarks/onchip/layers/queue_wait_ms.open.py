"""Frontend: median time from a query's due time to the flush that
served it, in ms (open loop only)."""
import numpy as np


def read(ctx):
    w = ctx.window.queue_wait_s
    if w is None or not w.size:
        return None
    return float(np.median(w)) * 1e3
