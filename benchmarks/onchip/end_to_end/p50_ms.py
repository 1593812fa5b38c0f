"""Closed loop: median latency of every query answered in the window, in
ms, from the hand-off of its batch until its results are on the host:
the time of one dispatch of the cell's batch."""
import numpy as np


def read(ctx):
    w = ctx.window
    return float(np.percentile(w.latency_s[w.answered], 50)) * 1e3
