"""Equivalence wall for the tile-list device scan (scan="tiles").

The flat work-queue path must be *bit-identical* to the padded-window path
through the full `MemANNSEngine.search`, across skewed cluster-size
distributions (one giant cluster + many tiny ones, uniform, more clusters
than distinct blobs so some end up empty/tiny), and the interpret-mode
kernel must match the pure-jnp oracle on hand-built inputs -- including an
all-dummy tile list, where the documented caller-side mask applies.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.index import IVFPQIndex
from repro.core.placement import place_clusters
from repro.kernels import adc_topk, ops, ref
from repro.kernels.adc_topk import adc_topk_tiles_kernel, adc_topk_windows_kernel
from repro.retrieval import MemANNSEngine, build_shards
from repro.retrieval.engine import make_dpu_mesh

NCODES = 256

# cluster-size distributions (k-means would flatten these, so the index is
# assembled directly; the online search path is exercised end to end)
SIZES = {
    "giant": [3000] + [40] * 15,            # one dominant + many tiny
    "uniform": [300] * 12,
    "empties": [500, 0, 120, 0, 0, 260, 64, 0, 300, 0, 7, 33],
}


def _engine_from_sizes(rng, sizes, *, m=4, dim=16, block_n=256,
                       use_cooc=False, scan="tiles"):
    """MemANNSEngine over a synthetic IVFPQ index with EXACT cluster sizes."""
    sizes = np.asarray(sizes, np.int64)
    c = len(sizes)
    n = int(sizes.sum())
    centroids = rng.normal(0, 50, (c, dim)).astype(np.float32)
    codebook = rng.normal(0, 1, (m, NCODES, dim // m)).astype(np.float32)
    codes = rng.integers(0, NCODES, (n, m)).astype(np.uint8)
    offsets = np.zeros(c + 1, np.int64)
    np.cumsum(sizes, out=offsets[1:])
    index = IVFPQIndex(
        centroids=centroids, codebook=codebook, codes=codes,
        vec_ids=np.arange(n, dtype=np.int32), offsets=offsets,
    )
    mesh = make_dpu_mesh()
    ndev = len(jax.devices())
    placement = place_clusters(
        sizes.astype(np.float64), np.ones(c) / c, ndev, centroids=centroids
    )
    shards = build_shards(
        index, placement, use_cooc=use_cooc, n_combos=16, block_n=block_n
    )
    return MemANNSEngine(
        index=index, placement=placement, shards=shards, mesh=mesh, scan=scan
    )


@pytest.mark.parametrize("kind", sorted(SIZES))
def test_tiles_equals_windows_end_to_end(kind):
    rng = np.random.default_rng(3)
    eng_t = _engine_from_sizes(rng, SIZES[kind])
    eng_w = dataclasses.replace(eng_t, scan="windows")
    qs = rng.normal(0, 50, (10, 16)).astype(np.float32)
    nprobe = 8
    d_t, i_t = eng_t.search(qs, nprobe=nprobe, k=10)
    d_w, i_w = eng_w.search(qs, nprobe=nprobe, k=10)
    np.testing.assert_array_equal(i_t, i_w)
    np.testing.assert_array_equal(d_t, d_w)  # bit-identical, not allclose

    # early pruning is an exact optimization: the bound-driven scan must
    # reproduce the unpruned reference bit for bit on both variants
    for eng in (eng_t, eng_w):
        eng_ref = dataclasses.replace(eng, prune=False)
        d_u, i_u = eng_ref.search(qs, nprobe=nprobe, k=10)
        np.testing.assert_array_equal(d_t, d_u)
        np.testing.assert_array_equal(i_t, i_u)

    # the whole point: fewer rows DMA'd on skewed layouts, never more
    plan_t = eng_t.plan_batch(qs, nprobe)
    plan_w = eng_w.plan_batch(qs, nprobe)
    rows_t = eng_t.scanned_rows(plan_t)
    rows_w = eng_w.scanned_rows(plan_w)
    assert rows_t <= rows_w
    if kind != "uniform":
        assert rows_t < rows_w


def test_tiles_equals_windows_cooc():
    """Same equivalence with co-occurrence re-encoded shards (uint16 path),
    and through the k-means-built engine rather than the synthetic index."""
    rng = np.random.default_rng(4)
    centers = rng.normal(0, 8, (12, 16)).astype(np.float32)
    xs = np.concatenate(
        [
            centers[i] + rng.normal(0, 0.5, (c, 16)).astype(np.float32)
            for i, c in enumerate([900] + [120] * 11)
        ]
    )
    qs = xs[rng.integers(0, len(xs), 8)].astype(np.float32)
    eng_t = MemANNSEngine.build(
        jax.random.PRNGKey(0), xs, n_clusters=12, m=4, block_n=256,
        use_cooc=True, n_combos=16, kmeans_iters=6, pq_iters=4, scan="tiles",
    )
    eng_w = dataclasses.replace(eng_t, scan="windows")
    d_t, i_t = eng_t.search(qs, nprobe=6, k=5)
    d_w, i_w = eng_w.search(qs, nprobe=6, k=5)
    np.testing.assert_array_equal(i_t, i_w)
    np.testing.assert_array_equal(d_t, d_w)


def test_tiles_equals_windows_cooc_synthetic_skew():
    """Co-occ shards over the exact 'empties' size distribution."""
    rng = np.random.default_rng(5)
    eng_t = _engine_from_sizes(rng, SIZES["empties"], use_cooc=True)
    eng_w = dataclasses.replace(eng_t, scan="windows")
    qs = rng.normal(0, 50, (6, 16)).astype(np.float32)
    d_t, i_t = eng_t.search(qs, nprobe=8, k=5)
    d_w, i_w = eng_w.search(qs, nprobe=8, k=5)
    np.testing.assert_array_equal(i_t, i_w)
    np.testing.assert_array_equal(d_t, d_w)


# --------------------------------------------------------------------- #
# interpret-mode kernel vs the pure-jnp oracle on hand-built inputs
# --------------------------------------------------------------------- #


def _hand_layout(rng, *, m=4, bn=8, slot_sizes=(13, 5, 0, 8)):
    """Device-style layout: block-aligned slots of raw uint8 codes."""
    starts, cursor = [], 0
    for s in slot_sizes:
        starts.append(cursor)
        cursor += -(-max(s, 1) // bn) * bn if s else bn  # keep slots distinct
    cap = max(cursor, bn)
    codes = rng.integers(0, NCODES, (cap, m)).astype(np.uint8)
    return codes, np.asarray(starts), np.asarray(slot_sizes), cap


def _emit_hand_tiles(pair_slot, n_valid, starts, bn, p_cap, t_cap):
    """Loop-reference tile emission for the kernel-level tests."""
    tp, tb, tr = [], [], []
    for p, s in enumerate(pair_slot):
        for t in range(-(-int(n_valid[p]) // bn)):
            tp.append(p)
            tb.append(starts[s] // bn + t)
            tr.append(t * bn)
    while len(tp) < t_cap:
        tp.append(p_cap)
        tb.append(0)
        tr.append(0)
    return (
        jnp.asarray(tp, jnp.int32),
        jnp.asarray(tb, jnp.int32),
        jnp.asarray(tr, jnp.int32),
    )


def test_tiles_kernel_matches_ref():
    rng = np.random.default_rng(7)
    m, bn, k = 4, 8, 4
    codes, starts, sizes, cap = _hand_layout(rng, m=m, bn=bn)
    pair_slot = np.asarray([0, 1, 3, 2, 0])  # slot 2 is empty (n_valid = 0)
    n_valid = sizes[pair_slot]
    p = len(pair_slot)
    a = m * NCODES + 1
    tables = jnp.asarray(rng.normal(0, 1, (p, a)).astype(np.float32))
    tile_pair, tile_block, tile_row0 = _emit_hand_tiles(
        pair_slot, n_valid, starts, bn, p, t_cap=8
    )

    tv, ti = ops.adc_topk_tiles(
        tables, jnp.asarray(codes.T), tile_pair, tile_block, tile_row0,
        jnp.asarray(n_valid), k, block_n=bn, add_offsets=True,
        interpret=True,
    )
    addrs_all = codes.astype(np.int32) + np.arange(m)[None, :] * NCODES
    for pi in range(p):
        nv = int(n_valid[pi])
        if nv == 0:
            continue  # undefined row by contract; engine masks these
        window = addrs_all[starts[pair_slot[pi]] : starts[pair_slot[pi]] + nv]
        rd, ri = ref.adc_topk_flat_ref(
            tables[pi : pi + 1], jnp.asarray(window), k, n_valid=nv
        )
        np.testing.assert_allclose(
            np.asarray(tv)[pi], np.asarray(rd)[0], rtol=1e-5, atol=1e-5
        )
        kk = min(k, nv)
        np.testing.assert_array_equal(
            np.asarray(ti)[pi][:kk], np.asarray(ri)[0][:kk]
        )
        assert (np.asarray(ti)[pi][kk:] == -1).all()


def test_all_dummy_tile_list_masks_to_windows_contract():
    """All-dummy queue + documented n_valid mask == windows kernel output."""
    rng = np.random.default_rng(9)
    m, bn, k, p = 4, 8, 3, 4
    codes, starts, _, cap = _hand_layout(rng, m=m, bn=bn)
    a = m * NCODES + 1
    tables = jnp.asarray(rng.normal(0, 1, (p, a)).astype(np.float32))
    n_valid = jnp.zeros((p,), jnp.int32)  # nothing scheduled anywhere
    t_cap = 6
    tile_pair = jnp.full((t_cap,), p, jnp.int32)  # every tile is a dummy
    tile_block = jnp.zeros((t_cap,), jnp.int32)
    tile_row0 = jnp.zeros((t_cap,), jnp.int32)

    tv, ti, _ = adc_topk_tiles_kernel(
        tables, jnp.asarray(codes.T), tile_pair, tile_block, tile_row0,
        n_valid, k=k, block_n=bn, add_offsets=True, interpret=True,
    )
    # apply the documented caller-side mask for pairs with no tiles
    tv = jnp.where((n_valid <= 0)[:, None], jnp.inf, tv)
    ti = jnp.where((n_valid <= 0)[:, None], -1, ti)

    wv, wi, _ = adc_topk_windows_kernel(
        tables, jnp.asarray(codes.T),
        (jnp.asarray(starts[:p]) // bn).astype(jnp.int32), n_valid,
        k=k, window=2 * bn, block_n=bn, add_offsets=True, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(tv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(ti), np.asarray(wi))


# --------------------------------------------------------------------- #
# early-pruning v2: bound-driven whole-tile skips stay exact
# --------------------------------------------------------------------- #


def test_all_dummy_tile_list_pruned_matches_unpruned():
    """Degenerate queue under pruning: every tile a dummy, finite bounds on
    -- still the windows-contract outputs and zero (masked) prune stats."""
    rng = np.random.default_rng(9)
    m, bn, k, p, q_n = 4, 8, 3, 4, 2
    codes = rng.integers(0, NCODES, (4 * bn, m)).astype(np.uint8)
    tables = jnp.asarray(
        np.abs(rng.normal(0, 1, (p, m * NCODES + 1))).astype(np.float32)
    )
    n_valid = jnp.zeros((p,), jnp.int32)
    tile_pair = jnp.full((6,), p, jnp.int32)
    tile_block = jnp.zeros((6,), jnp.int32)
    tile_row0 = jnp.zeros((6,), jnp.int32)
    kw = dict(k=k, block_n=bn, add_offsets=True, interpret=True)
    tv, ti = ops.adc_topk_tiles(
        tables, jnp.asarray(codes.T), tile_pair, tile_block, tile_row0,
        n_valid, **kw,
    )
    tvp, tip, stats = ops.adc_topk_tiles(
        tables, jnp.asarray(codes.T), tile_pair, tile_block, tile_row0,
        n_valid,
        pair_q=jnp.asarray([0, 1, 0, 1], jnp.int32),
        pair_lb=jnp.zeros((p,), jnp.float32),
        bound=jnp.full((q_n,), 7.5, jnp.float32),
        n_queries=q_n, with_stats=True, **kw,
    )
    mask = np.ones((p, 1), bool)  # every pair empty -> all rows masked
    np.testing.assert_array_equal(
        np.where(mask, np.inf, np.asarray(tv)),
        np.where(mask, np.inf, np.asarray(tvp)),
    )
    np.testing.assert_array_equal(
        np.where(mask, 0, np.asarray(stats)), np.zeros((p, 3), np.int32)
    )


def test_pruning_reports_skips_and_stays_exact_on_skew():
    """On the giant-cluster layout the bounds must skip real tiles (rows
    avoided > 0) while the merged results stay bit-identical -- the
    telemetry the serving stats and bench_prune build on."""
    rng = np.random.default_rng(13)
    eng = _engine_from_sizes(rng, SIZES["giant"])
    qs = rng.normal(0, 50, (10, 16)).astype(np.float32)
    plan = eng.plan_batch(qs, 8)
    assert plan.pruned and plan.pair_lb is not None
    assert np.isfinite(plan.query_bounds(10)).any()
    handle = eng.dispatch_plan(plan, 10)
    d_p, i_p = eng.collect(handle)
    stats = np.asarray(handle.prune_stats).sum(axis=0)
    assert stats[0] > 0, "no tile bodies skipped on a skewed layout"
    assert stats[1] > 0
    assert stats[0] <= eng.plan_tile_count(plan)

    eng_ref = dataclasses.replace(eng, prune=False)
    plan_u = eng_ref.plan_batch(qs, 8)
    handle_u = eng_ref.dispatch_plan(plan_u, 10)
    d_u, i_u = eng_ref.collect(handle_u)
    # unpruned: nothing skipped, nothing avoided (lanes 0-1); rows still
    # merge (lane 2)
    assert int(np.asarray(handle_u.prune_stats)[:, :2].sum()) == 0
    np.testing.assert_array_equal(d_p, d_u)
    np.testing.assert_array_equal(i_p, i_u)


def _rounds_merge(cur_v, cur_i, dists, row0, kth, qbound):
    """The k-round re-selection of the whole row, behind the whole-tile
    gate (tile min below the k-th and at or below the query bound)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, dists.shape, 1)
    v, i = ref.merge_topk_rounds_ref(
        cur_v, cur_i, dists, row0 + lane, cur_v.shape[1]
    )
    t_min = jnp.min(dists)
    go = (t_min < kth) & (t_min <= qbound)
    return jnp.where(go, v, cur_v), jnp.where(go, i, cur_i), jnp.int32(0)


@pytest.fixture
def fresh_traces():
    """Kernels traced inside the test are dropped on both sides of it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("kind", ["giant", "empties"])
def test_admission_merge_keeps_the_rounds_merge_skips_and_results(
    kind, monkeypatch, fresh_traces
):
    """On the skewed layouts the admission merge skips the same tiles and
    avoids the same rows (stats lanes 0-1) as the k-round merge, and the
    step's results are bit-identical."""
    rng = np.random.default_rng(13)
    eng = _engine_from_sizes(rng, SIZES[kind])
    qs = rng.normal(0, 50, (10, 16)).astype(np.float32)
    plan = eng.plan_batch(qs, 8)
    assert plan.pruned

    def run():
        handle = eng.dispatch_plan(plan, 10)
        d, i = eng.collect(handle)
        return d, i, np.asarray(handle.prune_stats)

    d_new, i_new, s_new = run()
    monkeypatch.setattr(adc_topk, "merge_admitted", _rounds_merge)
    jax.clear_caches()
    d_old, i_old, s_old = run()
    np.testing.assert_array_equal(s_new[:, :2], s_old[:, :2])
    assert s_new[:, 2].sum() > 0 and s_old[:, 2].sum() == 0
    np.testing.assert_array_equal(d_new, d_old)
    np.testing.assert_array_equal(i_new, i_old)
    if kind == "giant":
        assert s_new[:, 0].sum() > 0


def test_mutable_churn_pruned_bit_identical_at_zero_recompiles():
    """The mutable stream (inserts + tombstones + overfetch + bounded delta
    merge) under pruning: identical results to a prune=False twin fed the
    same mutations, with zero steady-state recompiles after warmup."""
    from repro.retrieval import ServingEngine

    rng = np.random.default_rng(11)
    sizes = [700] + [50] * 11
    eng = _engine_from_sizes(rng, sizes, block_n=64)
    eng_ref = dataclasses.replace(
        eng, prune=False, delta=None, _dev_arrays=None
    )
    srv = ServingEngine(
        eng, nprobe=6, k=5, micro_batch=4, mutable=True, delta_capacity=256
    )
    srv_ref = ServingEngine(
        eng_ref, nprobe=6, k=5, micro_batch=4, mutable=True,
        delta_capacity=256,
    )
    srv.warmup()
    srv_ref.warmup()
    warm_compiles = srv.stats.compiles

    next_id = int(sum(sizes))
    dim = eng.index.centroids.shape[1]
    for step in range(4):
        ids = np.arange(next_id, next_id + 8, dtype=np.int32)
        next_id += 8
        vecs = rng.normal(0, 50, (8, dim)).astype(np.float32)
        for s in (srv, srv_ref):
            s.insert(ids, vecs)
        dead = rng.integers(0, 700, 3)
        for s in (srv, srv_ref):
            s.delete(dead)
        qs = rng.normal(0, 50, (6, dim)).astype(np.float32)
        d_p, i_p = srv.search(qs)
        d_u, i_u = srv_ref.search(qs)
        np.testing.assert_array_equal(d_p, d_u, err_msg=f"step {step}")
        np.testing.assert_array_equal(i_p, i_u, err_msg=f"step {step}")
    assert srv.stats.compiles == warm_compiles, "churn stream recompiled"
    assert srv.stats.tiles_dispatched > 0
