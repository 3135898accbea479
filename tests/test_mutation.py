"""Mutable index: online inserts/deletes + incremental compaction.

The contract pinned here (the mutation subsystem's acceptance wall):

  * inserts are visible to the very next search (delta buffer);
  * tombstoned ids are never returned, before or after compaction;
  * an interleaved stream of >= 1k inserts and >= 200 deletes with at
    least one auto-compaction keeps recall@10 above the `test_recall.py`
    floor throughout and records ZERO steady-state recompiles, on both
    device scan variants;
  * post-compaction search results are bit-identical to a from-scratch
    `encode_index` (same trained centroids/codebooks -- re-running k-means
    on a different corpus could never be bit-comparable) + fresh
    `place_clusters` + `build_shards` over the surviving vectors.
"""

import dataclasses

import numpy as np
import jax
import pytest

from repro.core.delta import DeltaIndex
from repro.core.index import brute_force, encode_index, recall_at_k
from repro.core.placement import place_clusters
from repro.retrieval import MemANNSEngine, ServingEngine
from repro.retrieval.layout import build_shards

NPROBE = 8
K = 10
RECALL_FLOOR = 0.5
N0 = 12000  # base corpus rows (ids 0..N0-1)


@pytest.fixture(scope="module")
def base_engine(clustered_data):
    xs, centers, qs, hist = clustered_data
    return MemANNSEngine.build(
        jax.random.PRNGKey(0), xs, n_clusters=32, m=8,
        history_queries=hist, use_cooc=False, n_combos=32,
        block_n=256, kmeans_iters=8, pq_iters=6,
        mutable=True, delta_capacity=2048,
    )


def fresh(base_engine, **kw) -> MemANNSEngine:
    """Copy of the built engine with untouched mutation state."""
    return dataclasses.replace(
        base_engine,
        delta=DeltaIndex.create(base_engine.index.m, 2048),
        **kw,
    )


def rebuild_from_scratch(eng, xs_surv, ids_surv) -> MemANNSEngine:
    """From-scratch rebuild over the survivors with the same trained
    centroids/codebooks: encode + place + pack, no incremental paths."""
    idx = encode_index(eng.index.centroids, eng.index.codebook, xs_surv, ids_surv)
    pl = place_clusters(
        idx.cluster_sizes().astype(np.float64), eng.freqs,
        eng.shards.ndev, centroids=idx.centroids,
    )
    sh = build_shards(idx, pl, use_cooc=False, block_n=eng.shards.block_n)
    return MemANNSEngine(
        index=idx, placement=pl, shards=sh, mesh=eng.mesh, scan=eng.scan,
    )


def test_insert_visible_immediately(base_engine, clustered_data):
    xs, _, qs, _ = clustered_data
    eng = fresh(base_engine)
    new_ids = np.arange(N0, N0 + qs.shape[0], dtype=np.int32)
    assert eng.insert(new_ids, qs) == qs.shape[0]
    _, ids = eng.search(qs, nprobe=NPROBE, k=K)
    # each query's own (exactly matching) vector must rank first
    np.testing.assert_array_equal(ids[:, 0], new_ids)


def test_delete_filters_results(base_engine, clustered_data):
    xs, _, qs, _ = clustered_data
    eng = fresh(base_engine)
    _, ids0 = eng.search(qs, nprobe=NPROBE, k=K)
    victims = np.unique(ids0[:, 0])
    assert eng.delete(victims) == victims.size
    d1, ids1 = eng.search(qs, nprobe=NPROBE, k=K)
    assert not np.isin(ids1, victims).any()
    # the overfetch must keep full-k result rows despite the filtering
    assert (ids1 >= 0).all()


def test_delete_of_buffered_insert(base_engine, clustered_data):
    """An id deleted while still in the delta never surfaces anywhere."""
    xs, _, qs, _ = clustered_data
    eng = fresh(base_engine)
    new_ids = np.arange(N0, N0 + qs.shape[0], dtype=np.int32)
    eng.insert(new_ids, qs)
    eng.delete(new_ids[:10])
    _, ids = eng.search(qs, nprobe=NPROBE, k=K)
    assert not np.isin(ids, new_ids[:10]).any()
    np.testing.assert_array_equal(ids[10:, 0], new_ids[10:])
    eng.compact()
    _, ids2 = eng.search(qs, nprobe=NPROBE, k=K)
    assert not np.isin(ids2, new_ids[:10]).any()


def test_reinsert_of_tombstoned_id_rejected(base_engine, clustered_data):
    xs, _, qs, _ = clustered_data
    eng = fresh(base_engine)
    eng.delete(np.asarray([3]))
    with pytest.raises(ValueError, match="tombstoned"):
        eng.insert(np.asarray([3]), qs[:1])


def test_compaction_matches_scratch_rebuild(base_engine, clustered_data):
    """Engine-level: insert + delete + compact == from-scratch re-encode."""
    xs, centers, qs, _ = clustered_data
    eng = fresh(base_engine)
    rng = np.random.default_rng(5)
    new_ids = np.arange(N0, N0 + 300, dtype=np.int32)
    new_xs = (
        centers[rng.integers(0, 32, 300)]
        + rng.normal(0, 1, (300, 32)).astype(np.float32)
    )
    eng.insert(new_ids, new_xs)
    victims = rng.choice(N0, 80, replace=False)
    eng.delete(victims)
    rep = eng.compact()
    assert rep.merged == 300 and rep.dropped == 80
    assert not eng.mutation_active

    keep = ~np.isin(np.arange(N0), victims)
    xs_surv = np.concatenate([xs[keep], new_xs])
    ids_surv = np.concatenate([np.arange(N0)[keep], new_ids])
    ref = rebuild_from_scratch(eng, xs_surv, ids_surv)
    # the index itself is bit-identical ...
    np.testing.assert_array_equal(eng.index.codes, ref.index.codes)
    np.testing.assert_array_equal(eng.index.vec_ids, ref.index.vec_ids)
    np.testing.assert_array_equal(eng.index.offsets, ref.index.offsets)
    # ... and so are search results (placement may differ; results don't)
    d_c, i_c = eng.search(qs, nprobe=NPROBE, k=K)
    d_r, i_r = ref.search(qs, nprobe=NPROBE, k=K)
    np.testing.assert_array_equal(i_c, i_r)
    np.testing.assert_array_equal(d_c, d_r)


def test_mutable_serving_matches_engine(base_engine, clustered_data):
    """Micro-batched mutable serving == one-shot engine search, delta live."""
    xs, centers, qs, _ = clustered_data
    eng = fresh(base_engine)
    srv = ServingEngine(eng, nprobe=NPROBE, k=K, micro_batch=8, mutable=True)
    srv.warmup()
    rng = np.random.default_rng(7)
    new_ids = np.arange(N0, N0 + 100, dtype=np.int32)
    new_xs = (
        centers[rng.integers(0, 32, 100)]
        + rng.normal(0, 1, (100, 32)).astype(np.float32)
    )
    srv.insert(new_ids, new_xs)
    srv.delete(rng.choice(N0, 40, replace=False))
    sd, si = srv.search(qs)
    ed, ei = eng.search(qs, nprobe=NPROBE, k=K)
    np.testing.assert_array_equal(si, ei)
    np.testing.assert_allclose(sd, ed, rtol=1e-5, atol=1e-5)
    assert srv.stats.compiles == 0, srv.stats
    assert srv.stats.inserts == 100 and srv.stats.deletes == 40


@pytest.mark.parametrize("scan", ["tiles", "windows"])
def test_churn_stream(base_engine, clustered_data, scan):
    """The acceptance stream: interleaved inserts/deletes/searches.

    >= 1k inserts, >= 200 deletes, >= 1 auto-compaction; throughout:
    tombstoned ids never returned, recall@10 above the floor, zero
    steady-state recompiles; afterwards: bit-identical to a from-scratch
    rebuild over the survivors.
    """
    xs, centers, qs, _ = clustered_data
    eng = fresh(base_engine, scan=scan)
    # delta capacity is 2048: occupancy 0.5 => the 15th 72-row insert batch
    # (1080 buffered rows) crosses the threshold and auto-compacts mid-stream
    srv = ServingEngine(
        eng, nprobe=NPROBE, k=K, micro_batch=8, mutable=True,
        compact_occupancy=0.5, tombstone_limit=500,
    )
    srv.warmup()

    rng = np.random.default_rng(11)
    vecs = {i: xs[i] for i in range(N0)}  # live corpus (brute-force oracle)
    deleted: set[int] = set()
    next_id = N0
    recalls = []
    for round_ in range(16):
        b = 72
        ids = np.arange(next_id, next_id + b, dtype=np.int32)
        next_id += b
        new = (
            centers[rng.integers(0, 32, b)]
            + rng.normal(0, 1, (b, 32)).astype(np.float32)
        )
        srv.insert(ids, new)
        vecs.update(zip(ids.tolist(), new))
        live = np.fromiter(vecs.keys(), np.int64, count=len(vecs))
        victims = rng.choice(live, 14, replace=False)
        srv.delete(victims)
        for v in victims.tolist():
            vecs.pop(v)
            deleted.add(v)
        _, si = srv.search(qs)
        assert not np.isin(si, np.fromiter(deleted, np.int64)).any()
        if round_ % 5 == 4:  # recall checkpoint vs the live corpus
            ids_live = np.fromiter(vecs.keys(), np.int64, count=len(vecs))
            xs_live = np.stack([vecs[i] for i in ids_live.tolist()])
            _, t = brute_force(xs_live, qs, K)
            recalls.append(recall_at_k(si, ids_live[t]))

    st = srv.stats
    assert st.inserts >= 1000 and st.deletes >= 200
    assert st.compactions >= 1
    assert st.compiles == 0, st
    assert min(recalls) > RECALL_FLOOR, recalls

    # final compaction, then the bit-identity check vs a scratch rebuild
    srv.compact()
    assert not eng.mutation_active
    ids_live = np.fromiter(vecs.keys(), np.int64, count=len(vecs))
    xs_live = np.stack([vecs[i] for i in ids_live.tolist()])
    ref = rebuild_from_scratch(eng, xs_live, ids_live)
    d_c, i_c = eng.search(qs, nprobe=NPROBE, k=K)
    d_r, i_r = ref.search(qs, nprobe=NPROBE, k=K)
    np.testing.assert_array_equal(i_c, i_r)
    np.testing.assert_array_equal(d_c, d_r)
    np.testing.assert_array_equal(eng.index.vec_ids, ref.index.vec_ids)


def test_starved_overfetch_triggers_compaction(base_engine, clustered_data):
    """Deleting a query's entire k+overfetch neighbourhood starves the
    filter once (truncated rows, counted), which auto-compacts so the very
    next search serves full, exact results again."""
    xs, _, qs, _ = clustered_data
    eng = fresh(base_engine)
    srv = ServingEngine(
        eng, nprobe=NPROBE, k=K, micro_batch=8, mutable=True,
        tombstone_limit=10_000,  # keep the threshold out of the way
    )
    srv.warmup()
    # tombstone everything the main path can fetch (k + overfetch = 2K)
    # for query 0 -- more than the overfetch can absorb
    _, wide = eng.search(qs[:1], nprobe=NPROBE, k=2 * K + 8)
    victims = wide[0][wide[0] >= 0]
    srv.delete(victims)
    d1, i1 = srv.search(qs[:8])
    assert (i1[0] == -1).any(), "query 0 should have starved"
    assert not np.isin(i1, victims).any()
    assert srv.stats.starved_batches >= 1
    assert srv.stats.compactions >= 1  # starvation forced a compaction
    assert eng.delta.tombstone_count == 0
    # next search is exact: full k rows, matches a scratch rebuild
    d2, i2 = srv.search(qs[:8])
    assert (i2 >= 0).all()
    keep = ~np.isin(np.arange(N0), victims)
    ref = rebuild_from_scratch(eng, xs[keep], np.arange(N0)[keep])
    _, i_r = ref.search(qs[:8], nprobe=NPROBE, k=K)
    np.testing.assert_array_equal(i2, i_r)


def test_csr_invariant_validate(base_engine):
    idx = base_engine.index
    idx.validate()  # the built index satisfies the invariant
    bad = dataclasses.replace(idx, offsets=idx.offsets[:-1])
    with pytest.raises(ValueError, match="offsets"):
        bad.validate()
    bad2 = dataclasses.replace(
        idx, vec_ids=np.zeros_like(idx.vec_ids)
    )
    with pytest.raises(ValueError, match="duplicate"):
        bad2.validate()


def test_compaction_report_fields(base_engine, clustered_data):
    xs, centers, qs, _ = clustered_data
    eng = fresh(base_engine)
    # inactive delta -> no-op report
    rep0 = eng.compact()
    assert rep0.merged == 0 and rep0.devices_rewritten == 0
    eng.insert(np.asarray([N0], np.int32), qs[:1])
    rep = eng.compact()
    assert rep.merged == 1 and rep.clusters_changed == 1
    assert rep.devices_rewritten >= 1
    assert not rep.shapes_changed  # the build slack absorbed one row
    assert "compaction" in rep.summary()


def test_delta_topk_block_pads_when_buffer_smaller_than_k():
    """A delta buffer holding fewer rows than k returns its rows plus
    (+inf, -1) padding instead of failing in top_k."""
    import jax.numpy as jnp

    from repro.core.delta import delta_topk_block

    rng = np.random.default_rng(3)
    c, m, dsub, cap, k = 4, 4, 2, 8, 12
    centroids = rng.normal(0, 5, (c, m * dsub)).astype(np.float32)
    codebook = rng.normal(0, 1, (m, 256, dsub)).astype(np.float32)
    queries = centroids[:2] + 0.1
    ids = np.arange(100, 100 + cap, dtype=np.int32)
    alive = np.arange(cap) < 5
    d, i = delta_topk_block(
        jnp.asarray(centroids), jnp.asarray(codebook), jnp.asarray(queries),
        jnp.asarray(rng.integers(0, 256, (cap, m)).astype(np.uint8)),
        jnp.asarray(np.arange(cap, dtype=np.int32) % c),
        jnp.asarray(ids), jnp.asarray(alive),
        jnp.full((2,), jnp.inf, jnp.float32), nprobe=c, k=k,
    )
    d, i = np.asarray(d), np.asarray(i)
    assert d.shape == i.shape == (2, k)
    for q in range(2):
        assert sorted(i[q, :5].tolist()) == ids[:5].tolist()
        assert np.all(np.isfinite(d[q, :5]))
        assert np.all(np.diff(d[q, :5]) >= 0)
        assert np.all(np.isinf(d[q, 5:])) and np.all(i[q, 5:] == -1)
