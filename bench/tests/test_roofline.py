"""The scan roofline's byte count on a hand-built tile list."""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

PATH = Path(__file__).resolve().parents[1] / "metrics" / "scan_roofline.batch.py"
spec = importlib.util.spec_from_file_location("scan_roofline_batch", PATH)
roofline = importlib.util.module_from_spec(spec)
spec.loader.exec_module(roofline)


def plan():
    # two devices, P = 4 pairs; pair id 4 marks a padding tile
    tile_pair = np.array([[0, 1, 2, 3, 4, 4],
                          [0, 0, 1, 4, 4, 4]])
    tile_block = np.array([[7, 7, 8, 9, 7, 0],   # block 7 twice: one tile
                           [3, 4, 3, 5, 5, 5]])  # padding blocks ignored
    return tile_pair, tile_block, 4


def test_tile_steps_per_distinct_device_block():
    # device 0: block 7 twice, 8 and 9 once; device 1: block 3 twice, 4
    # once; padding tiles (pair 4) are no steps
    assert sorted(roofline.tile_steps([plan()]).tolist()) == [1, 1, 1, 2, 2]
    assert roofline.tile_steps([plan(), plan()]).size == 10


@pytest.mark.parametrize("skipped,read", [
    (0, 5), (1, 4), (2, 3), (3, 2), (4, 2), (5, 1), (6, 1), (7, 0), (99, 0)])
def test_skips_spare_the_tiles_with_fewest_steps(skipped, read):
    assert roofline.min_tiles([plan()], skipped) == read


def test_share_uses_the_scan_kernel_time_and_bandwidth():
    class Trace:
        def kernel_seconds(self, pattern):
            return 1e-3 if "adc_topk_tiles_kernel" in pattern else None

    class Ctx:
        trace = Trace()
        plans = [plan()]
        tiles_skipped = 1
        tile_bytes = 1000

        @staticmethod
        def hbm_bytes_per_s():
            return 1e9

    # 1 skip spares one single-step tile: 4 tiles x 1000 B at 1 GB/s =
    # 4 us against 1 ms
    assert abs(roofline.read(Ctx) - 0.4) < 1e-12
    Ctx.tiles_skipped = 7  # nothing left to count: no reading, never 0
    assert roofline.read(Ctx) is None
