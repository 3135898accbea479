"""Vectorized Algorithm 2 + densify vs the retained loop-reference oracle.

These are the correctness gates for the vectorized host-side online path:
the array implementation must cover every (query, cluster) pair exactly
once, only use replica devices, and reproduce the reference greedy's device
loads (hence `max_imbalance()`) exactly on integer cluster sizes.
"""

import numpy as np
import pytest

from repro.core.placement import place_clusters
from repro.core.scheduling import (
    densify_schedule,
    schedule_queries,
    schedule_queries_loop,
    schedule_to_arrays,
)


def _random_case(seed, q=40, nprobe=8, c=64, ndev=8, zipf=1.4):
    rng = np.random.default_rng(seed)
    sizes = (rng.zipf(zipf, c) * 20).clip(1, 20000).astype(np.int64)
    freqs = rng.zipf(1.3, c).astype(np.float64)
    pl = place_clusters(sizes, freqs, ndev, centroids=rng.normal(0, 1, (c, 8)))
    probed = np.stack([rng.choice(c, nprobe, replace=False) for _ in range(q)])
    return probed, sizes, pl


@pytest.mark.parametrize("seed", range(8))
def test_covers_every_pair_exactly_once(seed):
    probed, sizes, pl = _random_case(seed)
    sch = schedule_queries(probed, sizes, pl)
    got = sorted(zip(sch.pair_q.tolist(), sch.pair_c.tolist()))
    want = sorted(
        (q, int(c)) for q in range(probed.shape[0]) for c in probed[q]
    )
    assert got == want
    # every pair lands on a device holding a replica of its cluster
    for qi, c, d in zip(sch.pair_q, sch.pair_c, sch.pair_dev):
        assert int(d) in pl.replicas[int(c)]


@pytest.mark.parametrize(
    "seed,q,nprobe,ndev",
    [(s, q, p, n) for s in range(6) for q, p, n in [(40, 8, 8), (7, 3, 3)]]
    + [(0, 1, 1, 1), (1, 64, 16, 12), (2, 5, 1, 16)],
)
def test_matches_loop_oracle(seed, q, nprobe, ndev):
    """dev_load / max_imbalance / per-device pair lists all match exactly.

    Cluster sizes are integers, so every load accumulation is exact in
    float64 and the greedy tie-breaks are bit-identical between paths.
    """
    probed, sizes, pl = _random_case(seed, q=q, nprobe=nprobe, ndev=ndev)
    vec = schedule_queries(probed, sizes, pl)
    ref = schedule_queries_loop(probed, sizes, pl)
    np.testing.assert_array_equal(vec.dev_load, ref.dev_load)
    assert vec.max_imbalance() == ref.max_imbalance()
    assert vec.num_pairs() == ref.num_pairs()
    assert vec.assigned == ref.assigned


def test_matches_loop_oracle_heavy_replication():
    """One extremely hot cluster -> many replicas -> deep multi-replica path."""
    rng = np.random.default_rng(0)
    c, ndev = 32, 8
    sizes = np.full(c, 500, np.int64)
    freqs = np.ones(c)
    freqs[3] = 400.0  # paper Fig. 4a skew: forces ncpy > 1
    pl = place_clusters(sizes, freqs, ndev)
    assert len(pl.replicas[3]) > 1
    probed = np.stack(
        [np.r_[3, rng.choice(c, 7, replace=False)] for _ in range(64)]
    )
    vec = schedule_queries(probed, sizes, pl)
    ref = schedule_queries_loop(probed, sizes, pl)
    np.testing.assert_array_equal(vec.dev_load, ref.dev_load)
    assert vec.assigned == ref.assigned


def test_zero_size_cluster():
    """Empty clusters add no load and all go to the first least-loaded replica."""
    sizes = np.array([0, 100], np.int64)
    pl = place_clusters(np.array([1, 100]), np.array([5.0, 1.0]), 2)
    probed = np.zeros((6, 1), np.int64)  # everyone probes cluster 0
    vec = schedule_queries(probed, sizes, pl)
    ref = schedule_queries_loop(probed, sizes, pl)
    np.testing.assert_array_equal(vec.dev_load, ref.dev_load)
    assert vec.assigned == ref.assigned
    assert vec.dev_load.sum() == 0.0


@pytest.mark.parametrize("seed", range(5))
def test_densify_matches_reference(seed):
    """Vectorized densify == loop `schedule_to_arrays` on the same schedule."""
    probed, sizes, pl = _random_case(seed)
    vec = schedule_queries(probed, sizes, pl)
    ref = schedule_queries_loop(probed, sizes, pl)
    ndev = vec.ndev
    # synthetic dense local_slot covering every replica (slot = rank on dev)
    local_slot = np.full((ndev, sizes.shape[0]), -1, np.int32)
    for d in range(ndev):
        for s, c in enumerate(pl.dev_clusters[d]):
            local_slot[d, c] = s
    cap = int(vec.counts_per_dev().max())
    q_v, s_v, v_v = densify_schedule(vec, local_slot, cap)
    q_r, s_r, v_r = schedule_to_arrays(ref, local_slot, cap)
    np.testing.assert_array_equal(q_v, q_r)
    np.testing.assert_array_equal(s_v, s_r)
    np.testing.assert_array_equal(v_v, v_r)


def test_load_carry_zero_reproduces_unbiased_schedule():
    """None / all-zeros / omitted carry give bit-identical schedules."""
    probed, sizes, pl = _random_case(3)
    base = schedule_queries(probed, sizes, pl)
    for carry in (None, np.zeros(base.ndev)):
        sch = schedule_queries(probed, sizes, pl, load_carry=carry)
        np.testing.assert_array_equal(sch.pair_q, base.pair_q)
        np.testing.assert_array_equal(sch.pair_c, base.pair_c)
        np.testing.assert_array_equal(sch.pair_dev, base.pair_dev)
        np.testing.assert_array_equal(sch.dev_load, base.dev_load)


@pytest.mark.parametrize("seed", range(5))
def test_load_carry_matches_loop_oracle(seed):
    """Vectorized and loop schedulers stay in lockstep under integer carry
    (integer loads keep every float accumulation and tie-break exact)."""
    probed, sizes, pl = _random_case(seed)
    rng = np.random.default_rng(seed + 100)
    carry = rng.integers(0, 5000, pl.dev_load.shape[0]).astype(np.float64)
    vec = schedule_queries(probed, sizes, pl, load_carry=carry)
    ref = schedule_queries_loop(probed, sizes, pl, load_carry=carry)
    np.testing.assert_array_equal(vec.dev_load, ref.dev_load)
    assert vec.assigned == ref.assigned


def test_load_carry_sheds_hot_device():
    """A deliberately skewed carry makes the hot device's assigned rows
    drop versus the load-blind schedule (multi-replica pairs shed)."""
    rng = np.random.default_rng(0)
    c, ndev = 32, 8
    sizes = np.full(c, 500, np.int64)
    freqs = np.ones(c)
    freqs[3] = 400.0  # hot cluster -> multiple replicas -> greedy has choice
    pl = place_clusters(sizes, freqs, ndev)
    reps = pl.replicas[3]
    assert len(reps) > 1
    probed = np.stack(
        [np.r_[3, rng.choice(c, 7, replace=False)] for _ in range(64)]
    )
    blind = schedule_queries(probed, sizes, pl)
    hot = int(reps[0])
    carry = np.zeros(ndev)
    carry[hot] = 1e6  # device `hot` is running way behind
    biased = schedule_queries(probed, sizes, pl, load_carry=carry)
    # this batch's scan load on the hot device drops strictly
    assert biased.dev_load[hot] < blind.dev_load[hot]
    # and the carry never breaks the exactly-once coverage contract
    got = sorted(zip(biased.pair_q.tolist(), biased.pair_c.tolist()))
    want = sorted(zip(blind.pair_q.tolist(), blind.pair_c.tolist()))
    assert got == want
    for c_id, d in zip(biased.pair_c, biased.pair_dev):
        assert int(d) in pl.replicas[int(c_id)]


def test_load_carry_not_counted_in_dev_load():
    """Returned dev_load is the batch's own scan load, carry excluded."""
    probed, sizes, pl = _random_case(1)
    carry = np.full(pl.dev_load.shape[0], 123456.0)
    # uniform carry shifts every greedy start equally -> same schedule
    base = schedule_queries(probed, sizes, pl)
    sch = schedule_queries(probed, sizes, pl, load_carry=carry)
    np.testing.assert_array_equal(sch.pair_dev, base.pair_dev)
    np.testing.assert_array_equal(sch.dev_load, base.dev_load)
    assert sch.dev_load.sum() == base.dev_load.sum()


def test_load_carry_bad_shape_raises():
    probed, sizes, pl = _random_case(0)
    with pytest.raises(ValueError, match="load_carry"):
        schedule_queries(
            probed, sizes, pl,
            load_carry=np.zeros(pl.dev_load.shape[0] + 1),
        )


def test_densify_overflow_raises():
    probed, sizes, pl = _random_case(0)
    vec = schedule_queries(probed, sizes, pl)
    local_slot = np.zeros((vec.ndev, sizes.shape[0]), np.int32)
    cap = int(vec.counts_per_dev().max())
    with pytest.raises(ValueError, match="capacity"):
        densify_schedule(vec, local_slot, cap - 1)


def test_query_pair_index_lists_each_querys_slots():
    """Each (device, query) row lists that query's valid pair slots in
    ascending order, padded with P; padding pairs never appear."""
    from repro.core.scheduling import query_pair_index

    pair_q = np.asarray([[2, 0, 2, 1, 0, 0], [1, 1, 0, 0, 0, 0]], np.int32)
    valid = np.asarray(
        [[1, 1, 1, 1, 1, 0], [1, 1, 0, 0, 0, 0]], bool
    )
    qp = query_pair_index(pair_q, valid, n_queries=3, width=3)
    p = pair_q.shape[1]
    np.testing.assert_array_equal(
        qp,
        [
            [[1, 4, p], [3, p, p], [0, 2, p]],
            [[p, p, p], [0, 1, p], [p, p, p]],
        ],
    )
    with pytest.raises(ValueError, match="query index width"):
        query_pair_index(pair_q, valid, n_queries=3, width=1)
