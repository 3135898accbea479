"""Mean wait from a query's due time to the `search()` call that carries
it (host clock)."""


def read(ctx):
    w = ctx.window.wait_s
    if w is None or w.size == 0:
        return None
    return float(w.mean()) * 1e3
