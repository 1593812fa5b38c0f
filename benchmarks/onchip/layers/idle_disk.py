"""Disk I/O (store/): the device's idle time under the disk tier's spans
(``catapultdb.disk.*``: the per-lane block sets, the block fetch, the
speculation, the host rerank, the pins), in % of the traced window.
None where the program emits no such span."""
import span_reduce


def read(ctx):
    return span_reduce.idle_under(ctx, "catapultdb.disk.")
