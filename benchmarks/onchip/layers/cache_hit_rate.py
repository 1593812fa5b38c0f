"""Disk I/O: node-cache hits over hits plus block reads (the disk tier
only)."""


def read(ctx):
    hits, reads = ctx.lanes("cache_hits"), ctx.lanes("block_reads")
    if hits is None or reads is None:
        return None
    total = int(hits.sum()) + int(reads.sum())
    return int(hits.sum()) / total if total else None
