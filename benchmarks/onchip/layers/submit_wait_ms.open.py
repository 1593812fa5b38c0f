"""Frontend: mean time from a ticket's ``submit()`` to the dispatch of
its chunk, in ms, over the flushes that start in the traced window: the
program's own count (the ``wait_ms_sum`` and ``tickets`` arguments of
``catapultdb.frontend.flush``).  None where the program records none."""
import span_reduce


def read(ctx):
    t = span_reduce.of(ctx)
    flushes = [] if t is None else [
        f for f in t["flushes"]
        if f["span"] == "catapultdb.frontend.flush" and f.get("tickets")]
    if not flushes:
        return None
    return (sum(f["wait_ms_sum"] for f in flushes)
            / sum(f["tickets"] for f in flushes))
