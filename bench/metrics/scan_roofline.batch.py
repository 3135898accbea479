"""The ADC tiles scan's share of its HBM roofline.

The floor counts work no implementation can avoid, so it holds at or
below 100% after a scan that groups a cluster's queries onto one code
tile:

  * tiles: the distinct code tiles (device, code block) of each
    micro-batch's tile list, summed over the window.  A distinct tile
    goes unread only if every tile step on it was skipped, so the
    window's `tiles_skipped` spares at most the tiles with the fewest
    steps: those are taken off, fewest first, as far as the skips reach;
  * bytes: those tiles times `block_n x` the stored code width in bytes;
  * floor time: the bytes over the chip's HBM bandwidth (`peaks.json`).
    No FLOP term and no table bytes: a fused kernel need not read its
    look-up tables from HBM.

Share = floor time / the scan kernel's device time in the trace.
"""

import numpy as np

KERNEL = r"^adc_topk_tiles_kernel$"


def tile_steps(plans) -> np.ndarray:
    """Tile steps on each distinct (device, code block) of each plan; a
    plan is (tile_pair (ndev, T), tile_block (ndev, T), P), where
    tile_pair == P marks a padding tile."""
    steps = [np.zeros(0, np.int64)]
    for tile_pair, tile_block, pairs in plans:
        for d in range(tile_pair.shape[0]):
            real = tile_block[d][tile_pair[d] != pairs]
            steps.append(np.unique(real, return_counts=True)[1])
    return np.concatenate(steps)


def min_tiles(plans, tiles_skipped: int) -> int:
    """Distinct tiles that were read whichever steps the skips fell on."""
    steps = np.sort(tile_steps(plans))
    spared = int(np.searchsorted(np.cumsum(steps), int(tiles_skipped),
                                 side="right"))
    return steps.size - spared


def read(ctx):
    if ctx.trace is None or not ctx.plans:
        return None
    scan_s = ctx.trace.kernel_seconds(KERNEL)
    nbytes = min_tiles(ctx.plans, ctx.tiles_skipped) * ctx.tile_bytes
    if not scan_s or nbytes <= 0:
        return None
    return 100.0 * nbytes / ctx.hbm_bytes_per_s() / scan_s
