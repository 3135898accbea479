"""Host planning per micro-batch: the program's `plan` phase timer
(`ServingStats`), window total over the window's micro-batches."""


def read(ctx):
    if ctx.micro_batches <= 0:
        return None
    return ctx.plan_s / ctx.micro_batches * 1e3
