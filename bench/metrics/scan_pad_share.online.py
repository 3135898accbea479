"""Share of the tiles scan's grid steps in the window that served the
padding rows of micro-batches (program counter `upanns_scan_steps_total`,
kind `pad`, over all its kinds; `program_counters.py`)."""

import program_counters


def read(ctx):
    steps = program_counters.scan_steps()
    return None if steps is None else 100.0 * steps[1] / sum(steps)
