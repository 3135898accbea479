"""Shared benchmark utilities: timing, CSV emission, shared datasets.

Timings are wall-clock on the backend the harness runs on.  On the CPU
(interpret-mode Pallas + fake devices) they are *relative* signals between
code paths, not device metrics; only a run on a TPU measures the device.
Each bench reproduces the SHAPE of a paper figure.
"""

from __future__ import annotations

import time

import jax
import numpy as np

# (name, us_per_call, derived[, stats]) — stats is an optional JSON-able
# dict (e.g. a metrics-registry snapshot / per-phase breakdown) attached
# to the row in the BENCH_<pr>.json artifact but not printed in the CSV
ROWS: list[tuple] = []

# kernel-geometry autotune mode benches construct serving engines with;
# benchmarks/run.py overrides it from --autotune and stamps it on each row
AUTOTUNE_MODE = "off"


def time_fn(fn, *args, iters: int = 5, warmup: int = 2, **kw) -> float:
    """Median wall-time per call in microseconds (blocking on outputs)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def emit(name: str, us_per_call: float, derived: str = "",
         stats: dict | None = None):
    """Record one bench row.  `stats` (optional) is a JSON-able dict —
    typically `ServingStats.snapshot()` plus a per-phase breakdown — that
    rides into the BENCH_<pr>.json artifact as the row's ``metrics`` field
    (CSV output is unchanged)."""
    ROWS.append((name, us_per_call, derived) + ((stats,) if stats else ()))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def serving_obs(srv) -> dict:
    """The standard observability stamp for a serving bench row: the full
    metrics snapshot + the per-phase wall-time breakdown."""
    from repro.retrieval import PHASES

    st = srv.stats
    return {
        "snapshot": st.snapshot(),
        "phase_seconds": {p: st.phase_seconds(p) for p in PHASES},
        "p999_ms": 1e3 * st.p999_s(),
    }


def geometry_tag(eng) -> str:
    """Derived-column fragment recording the kernel geometry a row ran at."""
    return (
        f"block_n={eng.shards.block_n};rerank_block={eng.rerank_block};"
        f"tile_floor={eng.tile_floor}"
    )


def scan_ideal_bytes(eng, plan) -> int:
    """Ideal HBM bytes for one scan: code bytes the plan actually probes.

    `scanned_rows` is the plan's exact row count (post-pruning rows are
    *avoided work*, so the unpruned plan rows are the honest traffic
    bound); each row streams `width * itemsize` code bytes.  LUT reads are
    excluded (they live in fast memory after the first touch — the paper's
    WRAM residency argument), so the bound is the pure code-stream floor
    the roofline fraction divides by.
    """
    rows = int(eng.scanned_rows(plan))
    return rows * eng.shards.width * eng.shards.codes.dtype.itemsize


def small_system(
    n=15000, c=48, m=8, dim=32, use_cooc=False, seed=0, mesh=None
):
    """Shared small MemANNS system for online-path benches (`mesh`: the
    device mesh to shard over; default every device)."""
    import jax as _jax

    from repro.data import SkewedVectorDataset, make_clustered_vectors
    from repro.retrieval import MemANNSEngine

    xs, centers, _ = make_clustered_vectors(
        n, dim, c, pattern_pool=32, size_zipf=1.2, seed=seed
    )
    stream = SkewedVectorDataset(centers, popularity_zipf=1.1, seed=seed)
    eng = MemANNSEngine.build(
        _jax.random.PRNGKey(0), xs, c, m,
        history_queries=stream.queries(200, seed=1),
        use_cooc=use_cooc, n_combos=32, block_n=256,
        kmeans_iters=8, pq_iters=6, mesh=mesh,
    )
    return xs, stream, eng
