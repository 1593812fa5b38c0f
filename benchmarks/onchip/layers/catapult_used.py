"""Catapult layer: share of real lanes that started from a catapult
(SearchStats.used)."""


def read(ctx):
    used = ctx.lanes("used")
    return None if used is None or not used.size else float(used.mean())
