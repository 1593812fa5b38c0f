"""Engine host path, open loop: the device's idle time under the
engine's spans (``catapultdb.engine.*``), in % of the traced window.
None where the program emits no such span."""
import span_reduce


def read(ctx):
    return span_reduce.idle_under(ctx, "catapultdb.engine.")
