"""Engine host path (core/engine.py): the device's idle time under the
engine's spans (``catapultdb.engine.*``: the queries' upload, the jit
call, the reads of the results back to the host, the PQ rerank), in %
of the traced window.  None where the program emits no such span."""
import span_reduce


def read(ctx):
    return span_reduce.idle_under(ctx, "catapultdb.engine.")
