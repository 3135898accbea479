"""Device time of the ADC tiles scan kernel per real code tile it was
handed: the kernel's seconds in the trace over the window's `query` and
`pad` grid steps (program counter `upanns_scan_steps_total`;
`program_counters.py`)."""

import program_counters

KERNEL = r"^adc_topk_tiles_kernel$"


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.trace.kernel_seconds(KERNEL)
    tiles = program_counters.scan_steps(("query", "pad"))
    return None if s is None or tiles is None else s / sum(tiles) * 1e6
