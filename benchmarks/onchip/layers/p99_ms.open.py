"""Frontend: 99th percentile latency of every query answered in the
window, in ms, from its due time (open loop).  A few flushes of a
window that take several times the usual dispatch set it, so it swings
from run to run far more than the median does."""
import numpy as np


def read(ctx):
    w = ctx.window
    if w.queue_wait_s is None or not w.answered.any():
        return None
    return float(np.percentile(w.latency_s[w.answered], 99)) * 1e3
