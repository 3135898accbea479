"""Paper Fig. 17 / Fig. 12: impact of top-k size, and the §4.4 pruning win.

k in {1, 10, 100} on the fused kernel; derived column reports the pruning
effect: fraction of tile merges skipped on sorted-ascending data (worst
case none skipped) vs random order."""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit, small_system, time_fn
from repro.kernels import ops, ref

RNG = np.random.default_rng(5)


def run():
    m, n = 16, 1 << 14
    lut = jnp.asarray(RNG.normal(0, 1, (1, m, 256)).astype(np.float32))
    codes = jnp.asarray(RNG.integers(0, 256, (n, m)).astype(np.uint8))
    for k in (1, 10, 100):
        t = time_fn(lambda: ops.adc_topk(lut, codes, k, block_n=1024), iters=3)
        # pruning statistics: how many 1024-row tiles can improve the top-k?
        d = np.asarray(ref.adc_scan_ref(lut[0], codes))
        kth_running = np.inf
        skipped = 0
        tiles = n // 1024
        best = np.full(k, np.inf)
        for tix in range(tiles):
            tile = d[tix * 1024 : (tix + 1) * 1024]
            if tile.min() >= best[-1]:
                skipped += 1
                continue
            best = np.sort(np.concatenate([best, tile]))[:k]
        emit(
            f"fig17_topk_k{k}",
            t,
            f"tiles_pruned={skipped}/{tiles}",
        )

    # kernel-level tiles vs windows on a skewed synthetic layout: one giant
    # cluster forces the windows path to pad every pair to its window
    m2, bn = 8, 256
    sizes = [4096] + [64] * 15
    starts, cursor = [], 0
    for s in sizes:
        starts.append(cursor)
        cursor += -(-s // bn) * bn
    p = len(sizes)
    codes_dev = jnp.asarray(  # column-major (W, cap), as the engine ships
        RNG.integers(0, 256, (m2, cursor)).astype(np.uint8)
    )
    tables = jnp.asarray(
        RNG.normal(0, 1, (p, m2 * 256 + 1)).astype(np.float32)
    )
    n_valid = jnp.asarray(sizes, jnp.int32)
    starts_a = jnp.asarray(starts, jnp.int32)
    window = -(-max(sizes) // bn) * bn
    from repro.core.scheduling import emit_tiles

    total_tiles = sum(-(-s // bn) for s in sizes)
    tp, tb, tr = emit_tiles(
        np.arange(p, dtype=np.int32).reshape(1, p),
        np.ones((1, p), bool),
        np.asarray(starts, np.int32).reshape(1, p),
        np.asarray(sizes, np.int32).reshape(1, p),
        bn,
        total_tiles,
    )
    t_win = time_fn(
        lambda: ops.adc_topk_windows(
            tables, codes_dev, starts_a, n_valid, 10,
            window=window, block_n=bn, add_offsets=True,
        ),
        iters=3,
    )
    t_til = time_fn(
        lambda: ops.adc_topk_tiles(
            tables, codes_dev, jnp.asarray(tp[0]), jnp.asarray(tb[0]),
            jnp.asarray(tr[0]), n_valid, 10, block_n=bn, add_offsets=True,
        ),
        iters=3,
    )
    rows_w = p * window
    rows_t = total_tiles * bn
    emit(
        "tiles_vs_windows_kernel_skew",
        t_til,
        f"windows_us={t_win:.1f};rows_tiles={rows_t};rows_windows={rows_w};"
        f"rows_ratio={rows_t / rows_w:.3f}",
    )

    # end-to-end k sweep on the engine (paper Fig. 17 shape)
    xs, stream, eng = small_system(n=15000, c=48)
    qs = stream.queries(32, seed=2)
    import time as _t

    for k in (1, 10, 100):
        eng.search(qs, nprobe=8, k=k)
        t0 = _t.perf_counter()
        eng.search(qs, nprobe=8, k=k)
        wall = _t.perf_counter() - t0
        emit(f"fig17_engine_k{k}", 1e6 * wall / len(qs),
             f"qps={len(qs)/wall:.1f}")


if __name__ == "__main__":
    run()
