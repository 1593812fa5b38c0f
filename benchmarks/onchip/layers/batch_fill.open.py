"""Frontend: real lanes over the lanes dispatched, over the window: the
queries answered over the frontend's ``batches_dispatched`` times
``max_batch`` (it pads every chunk to max_batch)."""


def read(ctx):
    w = ctx.window
    if not w.batches:
        return None
    return float(w.answered.sum()) / (w.batches * w.max_batch)
