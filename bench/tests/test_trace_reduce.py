"""Trace reduction on a small trace recorded on one TPU v5e: two
micro-batches of 1000 queries at SIFT1B widths (200k rows, 4096 lists,
co-occurrence codes, pruning, exact re-rank) under `Tracer(profiler=True)`
and the harness's own `window` and `generator` spans."""

from pathlib import Path

import pytest

import run
import trace_reduce

TRACE = Path(__file__).resolve().parent / "data" / "sift_small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_profile(str(TRACE), run.SPANS)


def test_window_and_busy_time(reduced):
    assert reduced.n_devices == 1
    assert abs(reduced.window_s - 3.16853417) < 1e-6
    assert 0 < reduced.busy_s < reduced.window_s
    idle = sum(s for s, _ in reduced.gaps)
    assert abs(idle - (reduced.window_s - reduced.busy_s)) < 1e-6


def test_kernels_by_stable_name(reduced):
    k = reduced.kernel_s
    # the tiles scan runs in the tile-chunk loop's body, an instruction
    # named `closed_call`; it is found by its kernel function, charged its
    # own time and not again to the `while` around it
    assert k["adc_topk_tiles_kernel"] > 2.0
    assert "closed_call" not in k
    assert k.get("while", 0.0) < 1e-3
    assert 0.3 < k["ext_lut_pairs_kernel"] < 0.5
    assert 0 < k["rerank_dists_kernel"] < 1e-3
    assert not any("=" in name or "%" in name for name in k)
    assert abs(sum(k.values()) - reduced.busy_s) < 1e-3
    assert reduced.kernel_seconds(r"^adc_topk_tiles_kernel$") == \
        k["adc_topk_tiles_kernel"]
    assert reduced.kernel_seconds(r"^no_such_kernel$") is None


def test_pallas_kernels_named_by_their_function():
    names = trace_reduce.pallas_names(str(TRACE))
    assert sorted(set(names.values())) == [
        "adc_topk_tiles_kernel", "ext_lut_pairs_kernel",
        "rerank_dists_kernel"]
    scan = [op for op, k in names.items() if k == "adc_topk_tiles_kernel"]
    assert all(op.startswith("%closed_call.") for op in scan)


def test_idle_time_goes_to_the_innermost_host_span(reduced):
    idle = reduced.idle_by_label()
    assert max(idle, key=idle.get) == "densify"  # inside `plan`
    assert idle["densify"] > 0.4 and idle.get("plan", 0.0) < 0.01


def test_stable_names_and_self_times():
    assert trace_reduce.stable_name("%fusion.12 = f32[8] fusion(x)") == "fusion"
    assert trace_reduce.stable_name("%copy-done.3 = f32[2] copy-done(x)") \
        == "copy-done"
    assert trace_reduce.stable_name("%sort = (f32[1]) sort(x)") == "sort"
    nested = [(0, 10, "while"), (1, 4, "closed_call"), (5, 9, "closed_call"),
              (12, 13, "copy")]
    assert sorted(trace_reduce.self_times(nested)) == [
        ("closed_call", 3), ("closed_call", 4), ("copy", 1), ("while", 3)]
