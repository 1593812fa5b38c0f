"""Frontend: the device's idle time under the frontend's own spans
(``catapultdb.frontend.*``: pack, unpack, the call's bookkeeping, the
ingest pump), in % of the traced window; each idle nanosecond goes to
the innermost span over it.  None where the program emits no such
span."""
import span_reduce


def read(ctx):
    return span_reduce.idle_under(ctx, "catapultdb.frontend.")
