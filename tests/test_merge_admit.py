"""The scan's per-tile merge: admission-filtered insertion.

`adc_topk.merge_admitted` inserts only the tile rows below the running
k-th and at or below the query bound, one per round.  Pinned here:

  * against the k-round re-selection oracle (`ref.merge_topk_rounds_ref`)
    on random (running row, tile) cases -- ties, +inf entries, partial
    tiles, k in {1, 3, 64}, running rows full or not, bounds finite and
    infinite: every entry at or below the bound is identical in value,
    id and position, and with no bound the whole row is;
  * its rounds equal a host count of the insertions;
  * through the whole tiles kernel, on a hand-built queue with sound
    bounds, every pair's list and its [tiles skipped, rows avoided, rows
    merged] lanes equal a host model of the kernel.
"""

import bisect
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.kernels import ops, ref
from repro.kernels.adc_topk import merge_admitted

BN = 256
ROW0 = 1000
NCODES = 256


@functools.partial(jax.jit, static_argnames=("k",))
def _merge(cur_v, cur_i, dists, qbound, *, k):
    """`merge_admitted` in one interpret-mode kernel, as the scan runs it."""

    def kern(v_ref, i_ref, d_ref, qb_ref, ov, oi, on):
        v, i, n = merge_admitted(
            v_ref[...], i_ref[...], d_ref[...], ROW0,
            jnp.max(v_ref[...]), qb_ref[0, 0],
        )
        ov[...] = v
        oi[...] = i
        on[...] = jnp.full(on.shape, n, jnp.int32)

    return pl.pallas_call(
        kern,
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=True,
    )(cur_v, cur_i, dists, qbound)


def _host_insert(vals, ids, d, lane, row0):
    """Insert tile rows (admitted, in (value, lane) order) into a sorted
    running list while each beats its k-th; returns the insertions."""
    n = 0
    for j in np.lexsort((lane, d)):
        if not d[j] < vals[-1]:
            break
        at = bisect.bisect_right(vals, d[j])
        vals.insert(at, float(d[j]))
        ids.insert(at, int(row0 + lane[j]))
        vals.pop()
        ids.pop()
        n += 1
    return n


def _case(rng, k):
    """A running row (ascending, maybe +inf-tailed) and a partial tile,
    integer valued so ties are common."""
    full = rng.random() < 0.5
    n_cur = k if full else int(rng.integers(0, k))
    cur = np.sort(rng.integers(0, 40, n_cur)).astype(np.float32)
    cur_v = np.full(k, np.inf, np.float32)
    cur_v[:n_cur] = cur
    cur_i = np.full(k, -1, np.int32)
    cur_i[:n_cur] = rng.permutation(ROW0)[:n_cur]
    # ids of earlier rows are below the tile's; equal values keep id order
    cur_i[:n_cur] = np.sort(cur_i[:n_cur])
    rows = int(rng.integers(0, BN + 1))
    d = rng.integers(0, 48, BN).astype(np.float32)
    d[rows:] = np.inf
    qb = rng.choice([np.inf, float(rng.integers(0, 48)),
                     float(d[0]) if rows else np.inf])
    return cur_v, cur_i, d, np.float32(qb)


@pytest.mark.parametrize("k", [1, 3, 64])
def test_merge_matches_rounds_oracle_at_or_below_the_bound(k):
    rng = np.random.default_rng(k)
    lane = np.arange(BN)
    seen_bounded = seen_inf = 0
    for _ in range(40):
        cur_v, cur_i, d, qb = _case(rng, k)
        nv, ni, n = _merge(cur_v[None], cur_i[None], d[None],
                           np.full((1, 1), qb, np.float32), k=k)
        nv, ni, n = np.asarray(nv)[0], np.asarray(ni)[0], int(n[0, 0])
        ov, oi = ref.merge_topk_rounds_ref(
            jnp.asarray(cur_v[None]), jnp.asarray(cur_i[None]),
            jnp.asarray(d[None]), jnp.asarray(ROW0 + lane[None], jnp.int32),
            k,
        )
        ov, oi = np.asarray(ov)[0], np.asarray(oi)[0]
        keep = ov <= qb
        np.testing.assert_array_equal(nv <= qb, keep)
        np.testing.assert_array_equal(nv[keep], ov[keep])
        np.testing.assert_array_equal(ni[keep], oi[keep])
        if np.isinf(qb):
            seen_inf += 1
            np.testing.assert_array_equal(nv, ov)
            np.testing.assert_array_equal(ni, oi)
        else:
            seen_bounded += 1
        assert (nv[1:] >= nv[:-1]).all()
        # rounds == a host count of the insertions
        vals, ids = list(cur_v), list(cur_i)
        adm = (d < cur_v[-1]) & (d <= qb)
        want = _host_insert(vals, ids, d[adm], lane[adm], ROW0)
        assert n == want
        np.testing.assert_array_equal(nv, np.asarray(vals, np.float32))
        np.testing.assert_array_equal(ni, np.asarray(ids, np.int32))
    assert seen_bounded and seen_inf


def _host_tiles_scan(dists, tp, tr, tn, pair_q, pair_lb, bound, k, n_pairs):
    """Host model of the tiles kernel: skip rule, admission, insertion,
    running query bounds.  dists[p] holds pair p's window distances."""
    vals = [[np.inf] * k for _ in range(n_pairs)]
    ids = [[-1] * k for _ in range(n_pairs)]
    stats = np.zeros((n_pairs, 3), np.int64)
    sq = np.asarray(bound, np.float64).copy()
    for p, r0, rows in zip(tp, tr, tn):
        if p >= n_pairs:
            continue  # dummy tile
        q, kth = pair_q[p], vals[p][-1]
        if pair_lb[p] >= kth or pair_lb[p] > sq[q]:
            stats[p, 0] += rows > 0
            stats[p, 1] += rows
        else:
            d = dists[p][r0:r0 + rows]
            lane = np.arange(rows)
            adm = (d < kth) & (d <= sq[q])
            stats[p, 2] += _host_insert(vals[p], ids[p], d[adm], lane[adm],
                                        r0)
        sq[q] = min(sq[q], vals[p][-1])
    return np.asarray(vals, np.float32), np.asarray(ids, np.int32), stats


@pytest.mark.parametrize("k", [3, 8])
def test_rows_merged_lane_is_the_host_insertion_count(k):
    """Whole tiles kernel on a hand queue with sound bounds: lists and all
    three stats lanes equal the host model's."""
    rng = np.random.default_rng(11 + k)
    m, bn = 4, 8
    sizes = [21, 5, 0, 13, 8, 30]
    starts = np.concatenate([[0], np.cumsum([-(-max(s, 1) // bn) * bn
                                             for s in sizes])])[:-1]
    cap = int(starts[-1]) + 4 * bn
    codes = rng.integers(0, NCODES, (cap, m)).astype(np.uint8)
    pair_slot = np.asarray([0, 3, 1, 5, 2, 4, 0, 5, 4])
    pair_q = np.asarray([0, 0, 1, 1, 1, 2, 2, 2, 0], np.int32)
    p, q_n = len(pair_slot), 3
    n_valid = np.asarray(sizes)[pair_slot]
    # integer tables: every f32 sum is exact, and ties are common
    tables = rng.integers(0, 40, (p, m * NCODES)).astype(np.float32)
    tables[-1] += 1000  # a far pair: its tight bound skips its tiles
    addrs = codes.astype(np.int64) + np.arange(m)[None] * NCODES
    dists = [tables[j][addrs[starts[s]:starts[s] + n_valid[j]]].sum(-1)
             for j, s in enumerate(pair_slot)]
    # sound lower bounds, half of them (and the far pair's) tight
    lb = np.asarray(
        [(d.min() if j % 2 or j == p - 1 else np.floor(d.min() * rng.random()))
         if d.size else np.inf for j, d in enumerate(dists)], np.float32)
    finals = []
    for q in range(q_n):
        cand = np.sort(np.concatenate(
            [dists[j] for j in range(p) if pair_q[j] == q]))
        finals.append(cand[k - 1] + 1 if cand.size >= k else np.inf)
    bound = np.asarray([finals[0], np.inf, finals[2]], np.float32)
    # best-first queue of whole runs
    tp, tb, tr = [], [], []
    for j in np.argsort(lb, kind="stable"):
        for t in range(-(-int(n_valid[j]) // bn)):
            tp.append(j)
            tb.append(starts[pair_slot[j]] // bn + t)
            tr.append(t * bn)
    tp, tb, tr = (np.asarray(x, np.int32) for x in (tp, tb, tr))
    tn = np.clip(n_valid[tp] - tr, 0, bn)

    tv, ti, stats = ops.adc_topk_tiles(
        jnp.asarray(tables), jnp.asarray(codes.T), jnp.asarray(tp),
        jnp.asarray(tb), jnp.asarray(tr), jnp.asarray(n_valid), k,
        block_n=bn, add_offsets=True, interpret=True,
        pair_q=jnp.asarray(pair_q), pair_lb=jnp.asarray(lb),
        bound=jnp.asarray(bound), n_queries=q_n, with_stats=True,
    )
    hv, hi, hs = _host_tiles_scan(dists, tp, tr, tn, pair_q, lb, bound, k, p)
    has = n_valid > 0
    np.testing.assert_array_equal(np.asarray(tv)[has], hv[has])
    np.testing.assert_array_equal(np.asarray(ti)[has], hi[has])
    np.testing.assert_array_equal(np.asarray(stats), hs)
    assert hs[:, 0].sum() > 0, "no tile skipped: the queue tests too little"
    assert hs[:, 2].sum() > 0
