"""Set-up: process start to the first timed query (host clock)."""


def read(ctx):
    return ctx.setup_s
