"""Queries answered in the window over the window's seconds (from its
start to the last answer)."""


def read(ctx):
    w = ctx.window
    return float(w.answered.sum()) / w.seconds
