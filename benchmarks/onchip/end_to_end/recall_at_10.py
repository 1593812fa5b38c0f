"""Mean recall@10 of every query due in the window against exact k-NN
(the reference of onchip_harness.reference)."""


def read(ctx):
    return ctx.recall
