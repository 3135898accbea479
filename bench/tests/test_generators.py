"""Seed determinism of the corpus/query generator and the arrival
generator."""

import numpy as np

from traffic.generate import Mix, arrival_times
from traffic.vectors import VectorModel, VectorSource

MODEL = VectorModel(dim=16, n_centers=32, value_type="int8",
                    value_scale=12.0, value_offset=0.0)
BIG_SEED = 2**31 + 977


def test_vectors_repeat_for_a_seed_and_differ_across_seeds():
    a, b = VectorSource(MODEL, BIG_SEED), VectorSource(MODEL, BIG_SEED)
    np.testing.assert_array_equal(a.corpus(5000), b.corpus(5000))
    np.testing.assert_array_equal(a.queries(300, 1), b.queries(300, 1))
    assert not np.array_equal(a.queries(300, 0), a.queries(300, 1))
    c = VectorSource(MODEL, BIG_SEED + 1)
    assert not np.array_equal(a.corpus(5000), c.corpus(5000))
    # a run's queries come from its own seed over the corpus seed's world
    np.testing.assert_array_equal(a.queries(300, 1, seed=5),
                                  b.queries(300, 1, seed=5))
    assert not np.array_equal(a.queries(300, 1, seed=5),
                              a.queries(300, 1, seed=6))
    assert not np.array_equal(a.queries(300, 1, seed=5),
                              c.queries(300, 1, seed=5))


def test_vectors_keep_the_source_value_type():
    u8 = VectorSource(VectorModel(dim=8, n_centers=8, value_type="uint8",
                                  value_scale=12.0, value_offset=128.0), 3)
    xs = u8.corpus(2000)
    assert xs.dtype == np.uint8 and xs.shape == (2000, 8)
    assert VectorSource(MODEL, 3).corpus(100).dtype == np.int8


def test_cluster_sizes_follow_one_zipf_multiset():
    # every seed draws the same multiset of size weights, permuted
    def top(seed):
        src = VectorSource(MODEL, seed)
        centers = np.asarray(src._world[0])
        xs = src.corpus(20000).astype(np.float32)
        scaled = centers * MODEL.value_scale + MODEL.value_offset
        near = np.argmin(((xs[:, None] - scaled[None]) ** 2).sum(-1), 1)
        return np.sort(np.bincount(near, minlength=32))[::-1][:4]

    a, b = top(1), top(2)
    assert np.all(np.abs(a - b) < 0.15 * a)


MIX = Mix(name="t", arrivals="open_loop", micro_batch=8, rate_qps=200.0,
          burst_period_s=10.0, burst_start_s=4.0, burst_s=2.0,
          burst_factor=2.5)


def test_arrivals_repeat_for_a_seed_with_a_fixed_count():
    a = arrival_times(MIX, 30.0, BIG_SEED)
    np.testing.assert_array_equal(a, arrival_times(MIX, 30.0, BIG_SEED))
    b = arrival_times(MIX, 30.0, BIG_SEED + 1)
    assert a.shape == b.shape == (6000,)
    assert not np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 30.0


def test_arrivals_burst_at_the_stated_rate():
    a = arrival_times(MIX, 30.0, 5)
    burst = sum(((a >= s) & (a < s + 2.0)).sum() for s in (4.0, 14.0, 24.0))
    # 6 s at 2.5 x 200 q/s; the other 24 s at 0.625 x 200 q/s
    assert abs(burst - 3000) < 150
    assert abs((a.size - burst) - 3000) < 150
