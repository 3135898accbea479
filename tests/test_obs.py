"""Observability: metrics registry, span tracing, and the zero-perturbation
contract.

The wall pinned here:

  * log-bucketed histograms give exact quantile enclosures (the true
    rank-percentile always lies inside `quantile_bounds`) and the point
    estimate's relative error stays <= sqrt(growth) - 1; merge is
    lossless bucket addition;
  * the Prometheus rendering is well-formed and label values are escaped;
  * span trees nest correctly, sampling is deterministic (twin tracers
    record the same batches), and the ring stays bounded;
  * observability NEVER perturbs serving: a 200-query ragged stream
    returns bit-identical ids/distances with metrics + full tracing on
    vs fully off, at zero steady-state recompiles, on both scan paths
    and under mutable churn — and every real query of the stream is
    accounted for in exactly one recorded batch tree.
"""

import dataclasses
import json
import math

import numpy as np
import jax
import pytest

from repro.obs.metrics import (
    GROWTH,
    Histogram,
    MetricsRegistry,
    NULL_REGISTRY,
)
from repro.obs.trace import NULL_TRACER, Tracer
from repro.retrieval import PHASES, MemANNSEngine, ServingEngine

NPROBE = 8
K = 10


# ---------------------------------------------------------------------------
# metrics unit tests
# ---------------------------------------------------------------------------


def _true_rank_value(values, q):
    """The q-th percentile of the observed multiset, by rank (the thing
    `quantile_bounds` promises to enclose)."""
    s = sorted(values)
    rank = min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))
    return s[rank]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_histogram_quantile_enclosure(seed):
    rng = np.random.default_rng(seed)
    values = np.exp(rng.normal(-4, 2, 500))  # latencies-ish, heavy tail
    h = Histogram()
    for v in values:
        h.observe(float(v))
    rel_budget = math.sqrt(GROWTH) - 1.0 + 1e-9
    for q in (50.0, 90.0, 99.0, 99.9):
        lo, hi = h.quantile_bounds(q)
        truth = _true_rank_value(values, q)
        assert lo <= truth <= hi, (q, lo, truth, hi)
        est = h.quantile(q)
        assert lo <= est <= hi
        assert abs(est - truth) / truth <= rel_budget, (q, est, truth)


def test_histogram_zero_bucket_and_extrema():
    h = Histogram()
    for v in (-1.0, 0.0, 0.5, 2.0):
        h.observe(v)
    assert h.count == 4 and h.zero == 2
    assert h.min == -1.0 and h.max == 2.0
    lo, hi = h.quantile_bounds(25.0)  # rank 0 -> the zero bucket
    assert lo <= -1.0 <= hi or hi == 0.0
    assert h.quantile(100.0) <= h.max


def test_histogram_merge_is_lossless():
    rng = np.random.default_rng(3)
    a_vals = rng.exponential(0.01, 300)
    b_vals = rng.exponential(0.5, 200)
    a, b, both = Histogram(), Histogram(), Histogram()
    for v in a_vals:
        a.observe(float(v))
        both.observe(float(v))
    for v in b_vals:
        b.observe(float(v))
        both.observe(float(v))
    a.merge(b)
    assert a.buckets == both.buckets
    assert a.count == both.count and a.zero == both.zero
    assert a.min == both.min and a.max == both.max
    assert a.sum == pytest.approx(both.sum, rel=1e-12)
    for q in (50.0, 99.0, 99.9):
        assert a.quantile_bounds(q) == both.quantile_bounds(q)
    with pytest.raises(ValueError):
        a.merge(Histogram(growth=2.0))


def test_registry_families_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("upanns_test_total", "help", labels=("scan",))
    c.inc(scan="tiles")
    c.inc(2, scan="tiles")
    c.inc(scan="windows")
    assert c.get(scan="tiles") == 3.0
    assert c.get(scan="windows") == 1.0
    g = reg.gauge("upanns_test_gauge", "help")
    g.set(0.5)
    assert g.get() == 0.5
    # re-registration returns the same family; type conflicts are errors
    assert reg.counter("upanns_test_total", "help", labels=("scan",)) is c
    assert {n for n, _, _ in reg.catalog()} == {
        "upanns_test_total", "upanns_test_gauge"
    }


def test_registry_merge_aggregates():
    a, b = MetricsRegistry(), MetricsRegistry()
    for reg, n in ((a, 2), (b, 5)):
        c = reg.counter("upanns_m_total", "help")
        c.inc(n)
        h = reg.histogram("upanns_m_seconds", "help")
        for v in range(1, n + 1):
            h.observe(v * 0.01)
    a.merge(b)
    assert a.families()["upanns_m_total"].get() == 7.0
    assert a.families()["upanns_m_seconds"].labels().count == 7


def test_render_prometheus_escapes_and_quantiles():
    reg = MetricsRegistry()
    c = reg.counter("upanns_esc_total", "help", labels=("path",))
    c.inc(path='a"b\\c\nd')
    h = reg.histogram("upanns_esc_seconds", "help")
    h.observe(0.25)
    text = reg.render_prometheus()
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    for frag in ('quantile="0.5"', 'quantile="0.99"', 'quantile="0.999"',
                 "upanns_esc_seconds_sum", "upanns_esc_seconds_count",
                 "# TYPE upanns_esc_total counter"):
        assert frag in text, frag
    snap = reg.snapshot()
    json.dumps(snap)  # JSON-able
    assert "upanns_esc_seconds" in snap


def test_null_registry_is_inert():
    s = NULL_REGISTRY.counter("upanns_x_total", "help", labels=("a",))
    s.inc(a="y")
    s.labels(a="y").inc()
    assert s.get(a="y") == 0.0
    h = NULL_REGISTRY.histogram("upanns_y_seconds", "help")
    h.observe(1.0)
    assert h.labels().count == 0
    assert NULL_REGISTRY.catalog() == []
    assert NULL_REGISTRY.render_prometheus() == ""


# ---------------------------------------------------------------------------
# trace unit tests
# ---------------------------------------------------------------------------


def test_span_tree_nesting_and_export():
    tr = Tracer()
    b = tr.begin_batch(queries=4)
    with tr.span("plan", parent=b):
        with tr.span("schedule", root=False):
            pass
    with tr.span("collect", parent=b):
        pass
    tr.end_batch(b)
    (root,) = tr.roots()
    assert root.name == "batch" and root.args["queries"] == 4
    assert [c.name for c in root.children] == ["plan", "collect"]
    (sched,) = root.children[0].children
    assert sched.name == "schedule"
    for node in root.walk():
        assert node.t1 >= node.t0
        for child in node.children:
            assert child.t0 >= node.t0 - 1e-9
            assert child.t1 <= node.t1 + 1e-9
    events = tr.export_chrome()["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"batch", "plan", "schedule", "collect"}
    assert all(e["dur"] >= 0 for e in xs)


def test_child_only_spans_evaporate_outside_batch():
    tr = Tracer()
    with tr.span("schedule", root=False):  # no enclosing batch
        pass
    assert tr.roots() == []
    with tr.span("compaction"):  # root=True default: its own tree
        pass
    assert [s.name for s in tr.roots()] == ["compaction"]


def test_sampling_deterministic_twins():
    def record(tr, n=16):
        picked = []
        for i in range(n):
            b = tr.begin_batch(i=i)
            if b:
                picked.append(i)
            tr.end_batch(b)
        return picked

    a, b = Tracer(sample=0.25), Tracer(sample=0.25)
    pa, pb = record(a), record(b)
    assert pa == pb                       # twin runs sample identically
    assert len(pa) == 4                   # exactly every 4th batch
    assert a.batches_seen == 16 and a.batches_recorded == 4
    full = Tracer(sample=1.0)
    assert len(record(full)) == 16


def test_ring_stays_bounded():
    tr = Tracer(ring=4)
    for i in range(10):
        b = tr.begin_batch(i=i)
        tr.end_batch(b)
    roots = tr.roots()
    assert len(roots) == 4
    assert [r.args["i"] for r in roots] == [6, 7, 8, 9]
    assert tr.dropped == 6
    tr.clear()
    assert tr.roots() == []


def test_null_tracer_is_inert():
    b = NULL_TRACER.begin_batch(queries=1)
    assert not b
    with NULL_TRACER.span("plan", parent=b) as s:
        s.add("x", 0.0, 1.0)
    NULL_TRACER.end_batch(b)
    assert NULL_TRACER.roots() == []
    assert NULL_TRACER.export_chrome()["traceEvents"] == []


# ---------------------------------------------------------------------------
# serving integration: zero perturbation + trace completeness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine(clustered_data):
    xs, centers, qs, hist = clustered_data
    return MemANNSEngine.build(
        jax.random.PRNGKey(0), xs, n_clusters=32, m=8,
        history_queries=hist, use_cooc=False, n_combos=32,
        block_n=256, kmeans_iters=8, pq_iters=6,
    )


def _ragged_stream(qs, total=200, seed=11):
    """A 200-query stream in ragged chunks (sizes straddle micro_batch)."""
    rng = np.random.default_rng(seed)
    chunks, left = [], total
    while left:
        n = int(min(left, rng.integers(1, 40)))
        chunks.append(qs[rng.integers(0, qs.shape[0], n)])
        left -= n
    return chunks


@pytest.mark.parametrize("scan", ["tiles", "windows"])
def test_zero_perturbation_ragged_stream(engine, clustered_data, scan):
    """Obs fully on vs fully off over the same 200-query ragged stream:
    bit-identical ids and distances, zero steady-state compiles, and the
    trace accounts for every real query exactly once."""
    xs, _, qs, _ = clustered_data
    eng = dataclasses.replace(engine, scan=scan)
    tracer = Tracer(sample=1.0)
    srv_on = ServingEngine(eng, nprobe=NPROBE, k=K, micro_batch=16,
                           pipeline_depth=1, tracer=tracer)
    srv_off = ServingEngine(eng, nprobe=NPROBE, k=K, micro_batch=16,
                            pipeline_depth=1, metrics=False)
    srv_on.warmup()
    srv_off.warmup()
    chunks = _ragged_stream(qs)
    for chunk in chunks:
        eng.tracer = tracer
        d_on, i_on = srv_on.search(chunk)
        eng.tracer = NULL_TRACER
        d_off, i_off = srv_off.search(chunk)
        np.testing.assert_array_equal(i_on, i_off)
        np.testing.assert_array_equal(d_on, d_off)
    assert srv_on.stats.compiles == 0, srv_on.stats
    assert srv_off.stats.compiles == 0, srv_off.stats
    assert srv_on.stats.queries == 200 and srv_off.stats.queries == 200
    # the off side really is off: null registry, nothing rendered
    assert srv_off.stats.registry.render_prometheus() == ""
    # percentiles read the registry's histograms: 0.0 with metrics off
    assert srv_off.stats.latency_percentile(50) == 0.0
    assert srv_off.stats.prune_percentile(50) == 0.0

    # --- trace completeness: every query in exactly one batch tree --------
    roots = tracer.roots()
    assert tracer.batches_seen == tracer.batches_recorded == len(roots)
    assert sum(r.args["queries"] for r in roots) == 200
    for r in roots:
        names = [c.name for c in r.children]
        assert names.index("plan") < names.index("dispatch") < names.index(
            "collect"
        ), names
        assert r.args["scan"] == scan
        for node in r.walk():
            assert node.t1 >= node.t0
    # registry mirrors the same traffic
    st = srv_on.stats
    assert st.m_queries.get() == 200.0
    assert st.m_batches.get(scan=scan) == len(roots)
    assert st.m_latency.labels().count == len(roots)


def test_histogram_backed_percentiles(engine, clustered_data):
    xs, _, qs, _ = clustered_data
    tracer = Tracer(sample=1.0)
    srv = ServingEngine(engine, nprobe=NPROBE, k=K, micro_batch=8,
                        tracer=tracer)
    srv.warmup()
    tracer.clear()
    for _ in range(3):
        srv.search(qs)
    st = srv.stats
    h = st.m_latency.labels()
    assert h.count == st.batches > 0
    lo, hi = h.quantile_bounds(50.0)
    assert lo <= st.latency_percentile(50) <= hi
    assert st.p50_s() <= st.p99_s() + 1e-12
    assert st.p999_s() >= st.p99_s() - 1e-12
    # the exact p50 of the batch trees' durations (each tree spans its
    # batch's plan->collect sample, plus the span bookkeeping around it)
    # lies within one bucket of the histogram's enclosure
    roots = [r for r in tracer.roots() if r.name == "batch"]
    assert len(roots) == st.batches
    exact_p50 = _true_rank_value([r.t1 - r.t0 for r in roots], 50.0)
    assert lo / GROWTH <= exact_p50 <= hi * GROWTH
    assert st.latency_percentile(50) == pytest.approx(
        exact_p50, rel=2 * (math.sqrt(GROWTH) - 1) + 0.01
    )


def test_pipelined_wait_attribution(engine, clustered_data):
    xs, _, qs, _ = clustered_data
    srv = ServingEngine(engine, nprobe=NPROBE, k=K, micro_batch=8,
                        pipeline_depth=1)
    srv.warmup()
    for _ in range(3):
        srv.search(qs)
    st = srv.stats
    assert st.compiles == 0
    # every phase family carries samples; waits recorded on the depth-1 path
    for p in ("plan", "dispatch", "dispatch_wait", "collect_wait"):
        assert st.m_phase.labels(phase=p).count > 0, p
    assert st.dispatch_wait_s >= 0.0 and st.collect_wait_s > 0.0
    assert st.phase_seconds("dispatch_wait") == pytest.approx(
        st.dispatch_wait_s
    )
    span = sum(st.phase_seconds(p) for p in PHASES)
    assert span > 0.0


def test_mutable_churn_twin(clustered_data):
    """Obs on vs off under mutable churn (inserts + deletes + compaction):
    identical results, zero compiles, and a compaction span tree."""
    from repro.core.delta import DeltaIndex

    xs, centers, qs, hist = clustered_data
    base = MemANNSEngine.build(
        jax.random.PRNGKey(0), xs, n_clusters=32, m=8,
        history_queries=hist, use_cooc=False, n_combos=32,
        block_n=256, kmeans_iters=8, pq_iters=6,
        mutable=True, delta_capacity=1024,
    )

    def fresh():
        return dataclasses.replace(
            base, delta=DeltaIndex.create(base.index.m, 1024)
        )

    rng = np.random.default_rng(5)
    new_vecs = (
        centers[rng.integers(0, 32, 96)]
        + rng.normal(0, 1, (96, 32)).astype(np.float32)
    ).astype(np.float32)
    new_ids = np.arange(12000, 12096)

    tracer = Tracer(sample=1.0)
    outs = []
    for obs_on in (True, False):
        eng = fresh()
        srv = ServingEngine(
            eng, nprobe=NPROBE, k=K, micro_batch=8, mutable=True,
            tracer=tracer if obs_on else None,
            metrics=obs_on,
        )
        srv.warmup()
        step = []
        for r in range(3):
            srv.insert(new_ids[r * 32:(r + 1) * 32],
                       new_vecs[r * 32:(r + 1) * 32])
            srv.delete(np.arange(r * 10, r * 10 + 10))
            step.append(srv.search(qs[:16]))
        srv.compact()
        step.append(srv.search(qs[:16]))
        assert srv.stats.compiles == 0, srv.stats
        outs.append(step)
        if obs_on:
            assert srv.stats.inserts == 96 and srv.stats.deletes == 30
            assert srv.stats.m_inserts.get() == 96.0
            assert srv.stats.m_compactions.get() == 1.0
            assert srv.stats.m_tombstones.get() == 0.0  # cleared by compact
    for (d_on, i_on), (d_off, i_off) in zip(*outs):
        np.testing.assert_array_equal(i_on, i_off)
        np.testing.assert_array_equal(d_on, d_off)
    comp = [r for r in tracer.roots() if r.name == "compaction"]
    assert len(comp) == 1
    child_names = {c.name for c in comp[0].children}
    assert {"compact_index", "update_placement", "update_shards"} <= child_names


def test_serving_registry_renders_scrapable(engine, clustered_data):
    """One search stream -> a well-formed Prometheus doc with traffic."""
    xs, _, qs, _ = clustered_data
    srv = ServingEngine(engine, nprobe=NPROBE, k=K, micro_batch=8)
    srv.warmup()
    srv.search(qs)
    text = srv.stats.registry.render_prometheus()
    assert "# TYPE upanns_serving_queries_total counter" in text
    assert f"upanns_serving_queries_total {len(qs)}" in text
    assert 'upanns_phase_seconds' in text
    snap = srv.stats.snapshot()
    json.dumps(snap)
    compiles = snap["upanns_serving_compiles_total"]["samples"]
    assert compiles[0]["value"] == 0.0
