"""Pipelined vs serial serving equivalence + the async dispatch/collect split.

The double-buffered serving pipeline must be a pure latency optimization:
a ragged 300-query stream of mixed submit/flush/search calls returns
bit-identical (dists, ids) at pipeline depth 0 and depth 1, on both scan
paths, with zero recompiles after warmup.  The load-feedback EWMA updates
at dispatch time, so both depths also see identical schedules.
"""

import dataclasses

import numpy as np
import jax
import pytest

from repro.retrieval import InFlightSearch, MemANNSEngine, ServingEngine


@pytest.fixture(scope="module")
def engine(clustered_data):
    xs, centers, qs, hist = clustered_data
    return MemANNSEngine.build(
        jax.random.PRNGKey(0), xs, n_clusters=32, m=8,
        history_queries=hist, use_cooc=False, n_combos=32,
        block_n=256, kmeans_iters=8, pq_iters=6,
    )


def _ragged_stream(xs, total=300, seed=13):
    """Deterministic ragged op stream: (op, chunk) covering `total` queries."""
    rng = np.random.default_rng(seed)
    ops, left = [], total
    while left > 0:
        kind = rng.integers(0, 3)
        n = int(min(left, rng.integers(1, 40)))
        q = (
            xs[rng.integers(0, xs.shape[0], n)]
            + rng.normal(0, 0.1, (n, xs.shape[1]))
        ).astype(np.float32)
        if kind == 0:
            ops.append(("search", q))
        elif kind == 1:
            ops.append(("submit", q))
        else:
            ops.append(("submit", q))
            ops.append(("flush", None))
        left -= n
    ops.append(("flush", None))
    return ops


def _drive(srv, ops):
    """Run an op stream; returns the concatenated (dists, ids) outputs."""
    outs_d, outs_i = [], []
    for op, q in ops:
        if op == "search":
            d, i = srv.search(q)
        elif op == "submit":
            srv.submit(q)
            continue
        else:
            d, i = srv.flush()
        if d.shape[0]:
            outs_d.append(d)
            outs_i.append(i)
    return np.concatenate(outs_d), np.concatenate(outs_i)


@pytest.mark.parametrize("scan", ["tiles", "windows"])
def test_pipeline_depth_bit_identical_300_query_stream(
    engine, clustered_data, scan
):
    """Depth 0 vs depth 1 over a 300-query mixed submit/flush/search stream:
    bit-identical results, compiles == 0 after warmup, on both scans."""
    xs, _, _, _ = clustered_data
    eng = dataclasses.replace(engine, scan=scan)
    ops = _ragged_stream(xs)
    results = {}
    for depth in (0, 1):
        srv = ServingEngine(
            eng, nprobe=8, k=10, micro_batch=16, pipeline_depth=depth
        )
        srv.warmup()
        results[depth] = _drive(srv, ops)
        assert srv.stats.compiles == 0, (depth, srv.stats)
        assert srv.stats.queries == 300
        assert srv.stats.m_latency.labels().count == srv.stats.batches
        assert srv.stats.rows_scanned > 0
        if depth == 0:
            assert srv.stats.overlap_s == 0.0
        else:
            # >1 micro-batch per search/flush call occurs in this stream,
            # so some host planning must have been overlapped
            assert srv.stats.overlap_s > 0.0
            assert 0.0 < srv.stats.overlap_fraction() <= 1.0
    d0, i0 = results[0]
    d1, i1 = results[1]
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(d0, d1)  # bit-identical, not allclose


def test_pipeline_matches_plain_engine_without_feedback(engine, clustered_data):
    """With load feedback off, pipelined serving equals the one-shot engine
    search exactly (same schedules as the pre-pipeline serving layer)."""
    xs, _, qs, _ = clustered_data
    srv = ServingEngine(
        engine, nprobe=8, k=10, micro_batch=8,
        pipeline_depth=1, load_feedback=False,
    )
    srv.warmup()
    sd, si = srv.search(qs)
    ed, ei = engine.search(qs, nprobe=8, k=10)
    np.testing.assert_array_equal(si, ei)
    np.testing.assert_allclose(sd, ed, rtol=1e-5, atol=1e-5)


def test_load_feedback_biases_following_batches(engine, clustered_data):
    """The EWMA carry is updated at dispatch and fed into later plans."""
    xs, _, _, _ = clustered_data
    srv = ServingEngine(engine, nprobe=8, k=10, micro_batch=16)
    srv.warmup()
    assert (srv.load_carry() == 0).all()
    rng = np.random.default_rng(3)
    stream = xs[rng.integers(0, xs.shape[0], 48)].astype(np.float32)
    srv.search(stream)
    carry = srv.load_carry()
    assert carry.shape == (engine.shards.ndev,)
    assert carry.sum() > 0
    # the carry is an EWMA of per-batch rows: bounded by the largest report
    assert carry.max() <= max(
        srv.stats.rows_scanned, 1
    )


def test_dispatch_collect_composition(engine, clustered_data):
    """dispatch_plan + collect == execute_plan, and the handle's load
    report matches plan_dev_rows / rows actually scheduled."""
    xs, _, qs, _ = clustered_data
    plan = engine.plan_batch(qs, 8)
    handle = engine.dispatch_plan(plan, 10)
    assert isinstance(handle, InFlightSearch)
    assert handle.plan is plan
    np.testing.assert_array_equal(
        handle.dev_rows, engine.plan_dev_rows(plan)
    )
    hd, hi = engine.collect(handle)
    ed, ei = engine.execute_plan(plan, 10)
    np.testing.assert_array_equal(hi, ei)
    np.testing.assert_array_equal(hd, ed)
    # tiles load report: real tiles * block_n, one entry per device
    assert handle.dev_rows.shape == (engine.shards.ndev,)
    assert handle.dev_rows.sum() > 0


def test_plan_dev_rows_windows_counts_valid_rows(engine, clustered_data):
    """Windows-path load report = per-device valid rows of scheduled pairs
    (== the schedule's dev_load for integer cluster sizes)."""
    xs, _, qs, _ = clustered_data
    eng = dataclasses.replace(engine, scan="windows")
    plan = eng.plan_batch(qs, 8)
    rows = eng.plan_dev_rows(plan)
    np.testing.assert_array_equal(
        rows.astype(np.float64), plan.schedule.dev_load
    )


def test_key_follows_plan_scan_not_engine_scan(engine, clustered_data):
    """Bugfix: warm/compile tracking keys on plan.scan.  A plan created
    before flipping engine.scan still maps to the executable it will
    actually dispatch to, so stale plans neither miscount compiles nor
    mark the wrong executable warm."""
    xs, _, qs, _ = clustered_data
    eng = dataclasses.replace(engine, scan="tiles")
    srv = ServingEngine(eng, nprobe=8, k=10, micro_batch=16)
    srv.warmup()
    stale = srv._plan_micro_batch(qs[:16])   # tiles plan
    assert stale.scan == "tiles"
    eng.scan = "windows"                     # flipped after planning
    # the stale tiles plan hits the warmed tiles executable: no compile
    assert srv._key(stale) in srv._warm
    d, i = srv._collect_micro_batch(
        srv._dispatch_micro_batch(stale), 16, 0.0
    )
    assert srv.stats.compiles == 0, srv.stats
    # new plans follow the flipped engine scan and are counted as cold
    d2, i2 = srv.search(qs[:16])
    assert srv.stats.compiles > 0
    np.testing.assert_array_equal(i[:16], i2[:16])
