"""`correct` separates sound runs from the control and from faults.

Each case drives a whole run on the CPU at the test-only tiny size
(`run.run_cell`, which skips the harness's look for a chip): set-up, the
window, the check.  A sound run comes out correct; the control -- the
plain reference in bfloat16 put in the program's place -- does not; and
neither does a run with the served path broken underneath, once for each
fault these cells can have."""

import dataclasses
import json
import time

import numpy as np
import pytest

import cell
import checkout
import run
import repro.retrieval.engine as engine_mod
from repro.retrieval.engine import MemANNSEngine
from traffic.generate import Mix

SEED = 2**31 + 101


def tiny_cell(rerank: bool, mix="tiny_backlog") -> cell.Cell:
    cfg = json.loads((checkout.DATA / f"{checkout.TINY}.json").read_text())
    if not rerank:
        cfg["rerank"] = "off"
        cfg["check"]["limits"] = {"adc_rel_gap": 1e-5, "missed": 0,
                                  "bad_ids": 0, "misassigned": 0,
                                  "miscoded": 0, "lost_rows": 0}
    return cell.Cell(name="tiny", chips=1, config=cfg,
                     mix=Mix.load(checkout.DATA / f"{mix}.json"),
                     end_to_end=[], per_layer=[])


def drive(c, **kw):
    return run.run_cell(c, SEED, 1.0, False, time.perf_counter(), **kw)


def exceeded(checks: dict) -> list:
    return [n for n, v in checks.items() if v["value"] > v["limit"]]


@pytest.mark.parametrize("rerank", [False, True], ids=["pq", "rerank"])
def test_sound_run_is_correct_and_control_is_not(rerank):
    res = drive(tiny_cell(rerank), control=True)
    assert res["correct"], res["checks"]
    assert res["metrics"] == {}  # a CPU run reports no device metric
    assert exceeded(res["control_checks"]), res["control_checks"]


def _alter_answer(orig):
    def collect(self, handle):
        d, i = orig(self, handle)
        i = i.copy()
        i[:, 0] = (i[:, 0] + 1) % self.index.n_vectors
        return d, i
    return collect


def _half_batch(orig):
    def collect(self, handle):
        d, i = orig(self, handle)
        h = d.shape[0] // 2
        d, i = d.copy(), i.copy()
        d[h:2 * h], i[h:2 * h] = d[:h], i[:h]
        return d, i
    return collect


def _skip_rerank(orig):
    def dispatch_rerank(self, handle, queries, k_out):
        return dataclasses.replace(handle, out_d=handle.out_d[:, :k_out],
                                   out_i=handle.out_i[:, :k_out])
    return dispatch_rerank


FAULTS = [
    ("answer_altered", "collect", _alter_answer, False),
    ("answer_altered", "collect", _alter_answer, True),
    ("half_batch_left_out", "collect", _half_batch, False),
    ("half_batch_left_out", "collect", _half_batch, True),
    ("rerank_skipped", "dispatch_rerank", _skip_rerank, True),
]


@pytest.mark.parametrize(
    "name,attr,fault,rerank", FAULTS,
    ids=[f"{f[0]}-{'rerank' if f[3] else 'pq'}" for f in FAULTS])
def test_fault_is_not_correct(monkeypatch, name, attr, fault, rerank):
    monkeypatch.setattr(MemANNSEngine, attr,
                        fault(getattr(MemANNSEngine, attr)))
    res = drive(tiny_cell(rerank))
    assert not res["correct"], (name, res["checks"])
    assert res["failed"] > 0 and exceeded(res["checks"])


def _wrong_codeword(ix):
    codes = ix.codes.copy()
    codes[0, 0] = (int(codes[0, 0]) + 128) % 256
    return dataclasses.replace(ix, codes=codes)


def _row_in_wrong_list(ix):
    # the first rows of the two largest lists trade ids: each id now sits
    # in the other's list, with the other's code
    big = np.argsort(np.diff(ix.offsets))[-2:]
    a, b = ix.offsets[big]
    ids = ix.vec_ids.copy()
    ids[[a, b]] = ids[[b, a]]
    return dataclasses.replace(ix, vec_ids=ids)


def _row_dropped(ix):
    offsets = ix.offsets.copy()
    offsets[-1] -= 1
    return dataclasses.replace(ix, codes=ix.codes[:-1],
                               vec_ids=ix.vec_ids[:-1], offsets=offsets)


BUILD_FAULTS = [("wrong_codeword", _wrong_codeword, "miscoded"),
                ("row_in_wrong_list", _row_in_wrong_list, "misassigned"),
                ("row_dropped", _row_dropped, "lost_rows")]


@pytest.mark.parametrize("name,fault,number", BUILD_FAULTS,
                         ids=[f[0] for f in BUILD_FAULTS])
def test_build_fault_is_not_correct(monkeypatch, name, fault, number):
    build = engine_mod.build_index
    monkeypatch.setattr(engine_mod, "build_index",
                        lambda *a, **kw: fault(build(*a, **kw)))
    res = drive(tiny_cell(True))
    assert not res["correct"], (name, res["checks"])
    assert number in exceeded(res["checks"]), res["checks"]


def test_open_loop_run_is_correct():
    res = drive(tiny_cell(True, "tiny_online"))
    assert res["correct"], res["checks"]
    assert res["attempted"] == int(round(100.0 * 1.0))
    assert np.isfinite(res["checks"]["exact_gap"]["value"])


def test_configured_precision_and_build_device_hold(monkeypatch):
    import jax

    c = tiny_cell(True, "tiny_online")
    c.config["serving"]["matmul_precision"] = "highest"
    c.config["build"]["device"] = "cpu"
    seen = []
    build = run.build_system

    def recording_build(*a):
        seen.append((jax.config.jax_default_matmul_precision,
                     jax.config.jax_default_device))
        return build(*a)

    monkeypatch.setattr(run, "build_system", recording_build)
    res = drive(c)
    assert seen == [("highest", jax.devices("cpu")[0])], seen
    assert res["correct"], res["checks"]
    assert jax.config.jax_default_matmul_precision is None
    assert jax.config.jax_default_device is None
