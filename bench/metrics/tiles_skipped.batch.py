"""Share of dispatched code tiles whose body early pruning skipped
(`tiles_skipped / tiles_dispatched`, kernel-emitted counts)."""


def read(ctx):
    if ctx.tiles_dispatched <= 0:
        return None
    return 100.0 * ctx.tiles_skipped / ctx.tiles_dispatched
