"""Frontend, open loop: the device's idle time under the frontend's own
spans (``catapultdb.frontend.*``: pack, unpack, the flush's
bookkeeping, the ingest pump), in % of the traced window.  None where
the program emits no such span."""
import span_reduce


def read(ctx):
    return span_reduce.idle_under(ctx, "catapultdb.frontend.")
