"""Queries answered over the whole window, per second of it (host clock)."""


def read(ctx):
    return ctx.window.answered / ctx.window.elapsed_s
