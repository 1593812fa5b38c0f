"""Device, open loop: idle time inside a program's run on the device,
in % of the traced window."""
import span_reduce


def read(ctx):
    t = span_reduce.of(ctx)
    if t is None:
        return None
    return 100.0 * t["idle_in_program_s"] / t["window_s"]
