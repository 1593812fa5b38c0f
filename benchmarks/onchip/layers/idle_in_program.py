"""Device: idle time inside a program's run on the device (between the
operations of one ``XLA Modules`` interval), in % of the traced window:
gaps that the host does not cause."""
import span_reduce


def read(ctx):
    t = span_reduce.of(ctx)
    if t is None:
        return None
    return 100.0 * t["idle_in_program_s"] / t["window_s"]
