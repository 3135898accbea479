"""Device time of the ADC tiles scan kernel per micro-batch (trace)."""

KERNEL = r"^adc_topk_tiles_kernel$"


def read(ctx):
    if ctx.trace is None or ctx.micro_batches <= 0:
        return None
    s = ctx.trace.kernel_seconds(KERNEL)
    return None if s is None else s / ctx.micro_batches * 1e3
