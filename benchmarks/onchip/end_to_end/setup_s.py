"""Seconds from the start of the process to the start of the window:
start-up, corpus, graph load, create(), warm() and the warm-up prefix."""


def read(ctx):
    return ctx.setup_s
