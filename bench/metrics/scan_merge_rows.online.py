"""Tile rows the ADC tiles scan merged into its running top-k lists per
real code tile it was handed: program counter
`upanns_scan_rows_merged_total`, summed over devices, over the window's
`query` and `pad` grid steps (`upanns_scan_steps_total`;
`program_counters.py`).  None where the program has no such counter."""

import program_counters

MERGED = "upanns_scan_rows_merged_total"


def read(ctx):
    tiles = program_counters.scan_steps(("query", "pad"))
    merged, dev = [], 0
    while (n := program_counters.counter(MERGED, device=dev)) is not None:
        merged.append(n)
        dev += 1
    if tiles is None or not merged:
        return None
    return sum(merged) / sum(tiles)
