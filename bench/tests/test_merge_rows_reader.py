"""`scan_merge_rows.online`: rows merged per real tile step.

It reads the program counter `upanns_scan_rows_merged_total` (one series
per device) over `upanns_scan_steps_total`'s `query` and `pad` kinds from
`repro.obs.metrics.PROCESS_REGISTRY`, through `program_counters.py`, and
reads None where the program has no such counter, as an older program
has not.  After a run the registry holds exactly the window's merged
rows: set-up collects no micro-batch."""

import time

import numpy as np
import pytest

import cell
import checkout
import program_counters
import repro.obs.metrics as obs_metrics
import run
from repro.retrieval.serving import ServingStats
from test_control import SEED, tiny_cell

NAME = "scan_merge_rows.online"
MERGED = "upanns_scan_rows_merged_total"
STEPS = "upanns_scan_steps_total"


def registry_with(steps, merged):
    """A process registry holding the given step counts and per-device
    rows merged (None: without that family)."""
    reg = obs_metrics.MetricsRegistry()
    if steps is not None:
        fam = reg.counter(STEPS, "steps", ("kind",))
        for k, v in steps.items():
            fam.inc(v, kind=k)
    if merged is not None:
        fam = reg.counter(MERGED, "merged", ("device",))
        for dev, v in enumerate(merged):
            fam.inc(v, device=dev)
    return reg


def read(ctx=None):
    return cell.reader(NAME, checkout.REPO)(ctx)


STEP_COUNTS = {"query": 600, "pad": 200, "bucket": 200}


def test_rows_merged_per_real_tile(monkeypatch):
    # (3000 + 1000) rows merged over 600 + 200 real tiles: 5 per tile
    for merged, want in (([3000, 1000], pytest.approx(5.0)),
                         ([800], pytest.approx(1.0)),
                         (None, None), ([], None)):
        monkeypatch.setattr(obs_metrics, "PROCESS_REGISTRY",
                            registry_with(STEP_COUNTS, merged))
        assert read() == want
    monkeypatch.setattr(obs_metrics, "PROCESS_REGISTRY",
                        registry_with(None, [800]))
    assert read() is None
    # an older program: no process registry at all
    monkeypatch.delattr(obs_metrics, "PROCESS_REGISTRY")
    assert read() is None


def test_run_leaves_the_window_rows_merged_in_the_process_registry(
    monkeypatch,
):
    """A fresh process registry holds, after a tiny CPU run, the rows
    merged of the window's collected micro-batches, per device."""
    monkeypatch.setattr(obs_metrics, "PROCESS_REGISTRY",
                        obs_metrics.MetricsRegistry())
    import repro.retrieval.serving as serving
    monkeypatch.setattr(serving, "PROCESS_REGISTRY",
                        obs_metrics.PROCESS_REGISTRY)
    noted = []
    note = ServingStats.note_rows_merged

    def spy(self, per_device):
        noted.append(np.asarray(per_device).copy())
        return note(self, per_device)

    monkeypatch.setattr(ServingStats, "note_rows_merged", spy)
    seen = []
    merge_rows = cell.reader(NAME, checkout.REPO)

    def reader(name, root):
        def read_ctx(ctx):
            seen.append((ctx, program_counters.counter(MERGED, device=0),
                         merge_rows(ctx)))
            return None
        return read_ctx

    monkeypatch.setattr(run.cell_mod, "reader", reader)
    c = tiny_cell(True, "tiny_online")
    c.end_to_end = [{"name": NAME, "unit": "rows/step"}]
    run.run_cell(c, SEED, 1.0, False, time.perf_counter())
    ((ctx, merged, value),) = seen
    assert len(noted) == ctx.micro_batches > 0
    assert merged == float(np.sum(noted, axis=0)[0]) > 0
    tiles = sum(program_counters.scan_steps(("query", "pad")))
    assert value == pytest.approx(merged / tiles)
