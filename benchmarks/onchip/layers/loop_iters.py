"""Traversal: mean over dispatches of the largest hop count among the
dispatch's lanes, the trip count of the lockstep search loop."""
import numpy as np


def read(ctx):
    per = [int(np.max(s.hops)) for s in ctx.window.stats if len(s.hops)]
    return float(np.mean(per)) if per else None
