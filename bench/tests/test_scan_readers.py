"""The scan-work readers and the program's process-lifetime counters.

`scan_pad_share.online`, `scan_bucket_share.online` and
`scan_us_per_tile.online` read the program counter
`upanns_scan_steps_total` from `repro.obs.metrics.PROCESS_REGISTRY`
through `program_counters.py`; each reads None where the program has no
such registry or counter.  After a run, the registry holds exactly the
window's collected micro-batches: set-up counts none."""

import time
import types

import pytest

import cell
import checkout
import program_counters
import repro.obs.metrics as obs_metrics
import run
from repro.retrieval.serving import ServingStats
from test_control import SEED, tiny_cell

STEPS = "upanns_scan_steps_total"
KINDS = ("query", "pad", "bucket")


def registry_with(steps):
    """A process registry holding the given step counts (None: a
    registry without the family)."""
    reg = obs_metrics.MetricsRegistry()
    if steps is not None:
        fam = reg.counter(STEPS, "steps", ("kind",))
        for k, v in steps.items():
            fam.inc(v, kind=k)
    return reg


def ctx_with(kernel_s=None):
    """A reader's ctx with, where given, the scan kernel's device seconds
    in the trace."""

    class Trace:
        def kernel_seconds(self, pattern):
            return kernel_s if "adc_topk_tiles_kernel" in pattern else None

    return types.SimpleNamespace(
        trace=None if kernel_s is None else Trace())


def read(name, ctx):
    return cell.reader(name, checkout.REPO)(ctx)


STEP_COUNTS = {"query": 600, "pad": 200, "bucket": 200}


@pytest.mark.parametrize("name,want", [
    ("scan_pad_share.online", 20.0),
    ("scan_bucket_share.online", 20.0),
])
def test_shares_of_the_scan_steps(monkeypatch, name, want):
    for steps, expect in ((STEP_COUNTS, pytest.approx(want)), (None, None),
                          ({k: 0 for k in KINDS}, None)):
        monkeypatch.setattr(obs_metrics, "PROCESS_REGISTRY",
                            registry_with(steps))
        assert read(name, ctx_with()) == expect
    # an older program: no process registry at all
    monkeypatch.delattr(obs_metrics, "PROCESS_REGISTRY")
    assert read(name, ctx_with()) is None


def test_us_per_tile_is_kernel_time_over_real_tiles(monkeypatch):
    # 0.4 s of kernel time over 600 + 200 real tiles: 500 us per tile
    monkeypatch.setattr(obs_metrics, "PROCESS_REGISTRY",
                        registry_with(STEP_COUNTS))
    name = "scan_us_per_tile.online"
    assert read(name, ctx_with(0.4)) == pytest.approx(500.0)
    assert read(name, ctx_with()) is None
    monkeypatch.setattr(obs_metrics, "PROCESS_REGISTRY", registry_with(None))
    assert read(name, ctx_with(0.4)) is None
    monkeypatch.delattr(obs_metrics, "PROCESS_REGISTRY")
    assert read(name, ctx_with(0.4)) is None


def test_counter_lookup_matches_labels(monkeypatch):
    monkeypatch.setattr(obs_metrics, "PROCESS_REGISTRY",
                        registry_with(STEP_COUNTS))
    assert program_counters.counter(STEPS, kind="pad") == 200.0
    assert program_counters.counter(STEPS, kind="other") is None
    assert program_counters.counter(STEPS) is None
    assert program_counters.counter("upanns_no_such_counter_total") is None
    assert program_counters.scan_steps(("query", "pad")) == [600.0, 200.0]


def test_run_leaves_the_window_steps_in_the_process_registry(monkeypatch):
    """A fresh process registry holds, after a tiny CPU run, the steps of
    the window's collected micro-batches and nothing from set-up."""
    monkeypatch.setattr(obs_metrics, "PROCESS_REGISTRY",
                        obs_metrics.MetricsRegistry())
    import repro.retrieval.serving as serving
    monkeypatch.setattr(serving, "PROCESS_REGISTRY",
                        obs_metrics.PROCESS_REGISTRY)
    noted = []
    note = ServingStats.note_scan_steps

    def spy(self, steps):
        noted.append(tuple(steps))
        return note(self, steps)

    monkeypatch.setattr(ServingStats, "note_scan_steps", spy)
    seen = []

    def reader(name, root):
        def read_ctx(ctx):
            seen.append((ctx, program_counters.scan_steps()))
            return None
        return read_ctx

    monkeypatch.setattr(run.cell_mod, "reader", reader)
    c = tiny_cell(True, "tiny_online")
    c.end_to_end = [{"name": "scan_pad_share.online", "unit": "%"}]
    run.run_cell(c, SEED, 1.0, False, time.perf_counter())
    ((ctx, steps),) = seen
    assert c.config["build"]["scan"] == "tiles"
    assert len(noted) == ctx.micro_batches > 0
    assert steps == [float(sum(n[i] for n in noted)) for i in range(3)]
    assert steps[0] > 0 and min(steps) >= 0
    assert steps[0] + steps[1] == ctx.tiles_dispatched
