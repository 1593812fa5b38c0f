"""Open loop: median latency of every query answered in the window, in
ms, from its due time until its results are on the host.  A stalled
flush delays every query due behind it, so this median moves with the
queue and not with one dispatch, as the closed cells' p50_ms does."""
import numpy as np


def read(ctx):
    w = ctx.window
    return float(np.percentile(w.latency_s[w.answered], 50)) * 1e3
