"""Device: 100 x (1 - union of device-operation intervals over the
traced window)."""


def read(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace["idle_share"]
