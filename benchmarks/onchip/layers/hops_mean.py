"""Traversal: mean node expansions per query (SearchStats.hops)."""


def read(ctx):
    hops = ctx.lanes("hops")
    return None if hops is None or not hops.size else float(hops.mean())
