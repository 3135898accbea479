"""Peak device memory after the window (`peak_bytes_in_use` of the
fullest chip), in GB."""


def read(ctx):
    return ctx.peak_bytes / 1e9 if ctx.peak_bytes > 0 else None
