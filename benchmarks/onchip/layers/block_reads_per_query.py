"""Disk I/O: mean blocks read from the store per query
(SearchStats.block_reads; the disk tier only)."""


def read(ctx):
    reads = ctx.lanes("block_reads")
    return None if reads is None or not reads.size else float(reads.mean())
