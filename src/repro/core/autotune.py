"""Warmup-time kernel-geometry autotuner (ROADMAP item 4).

The Pallas kernels are geometry-parameterized end-to-end — `block_n` (scan
tile height, baked into the shard layout), `rerank_block` (re-rank
candidate-block width) and `tile_floor` (tile work-queue capacity floor)
thread from `MemANNSEngine` knobs down into the kernels — but the right
values depend on the backend: DRIM-ANN (PAPERS.md) shows ANNS on commodity
PIM lives or dies on per-device-generation parameter tuning, and the
UpANNS §5 wins come from matching kernel granularity to the hardware's
bank/WRAM geometry.  This module measures instead of guessing:

  * `sweep_engine` times a small candidate grid of geometries on synthetic
    shard-shaped data (same width / dtype / table size / addressing mode as
    the engine's real shards, so the executables exercised are the ones
    production will run) and picks the argmin;
  * the pick persists to a versioned JSON cache
    (`~/.cache/repro/autotune-<backend>-v<version>.json`) keyed by
    (device kind, shard shape bucket, k bucket), so production warmup pays
    the sweep once per (hardware, config) and every later process start
    reads the cached winner;
  * `configs/autotune_defaults.json` (in-repo) is the fallback for
    backends never swept on this machine — its entries are honest: an
    unmeasured backend maps to `block_n=0` ("keep the build-time
    geometry"), never to another machine's numbers.

Bit-identity to the untuned path is guaranteed by construction, not by
testing alone: geometry is data layout (where tile boundaries fall, how
wide a re-rank block is), and every selection the kernels make is
boundary-invariant — the same contract as the tiles==windows equivalence
(see `MemANNSEngine.retile` and tests/test_autotune.py's invariance wall).

`ServingEngine(autotune="off"|"cache"|"sweep")` is the consumer: "cache"
(default) applies a cached/default geometry at warmup, "sweep" measures
and persists first, "off" serves the build-time geometry untouched.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import time

import numpy as np

from repro.kernels import ops

# bump when the cache entry schema OR the meaning of a tuned knob changes:
# both the cache filename and the in-file version field carry it, so stale
# caches from older builds are ignored (never misapplied)
CACHE_VERSION = 1

DEFAULT_BLOCK_NS = (256, 512, 1024)
SWEEP_TILES = 8  # synthetic scan length per candidate, in tiles


@dataclasses.dataclass(frozen=True)
class KernelGeometry:
    """One tunable kernel-geometry point (the autotuner's unit of work).

    block_n: scan tile height (rows per kernel grid step); 0 = keep the
      engine's build-time tile height.  Applying a different value retiles
      the shard layout (`MemANNSEngine.retile`) — results bit-identical.
    rerank_block: re-rank kernel candidate-block width; 0 = kernel default.
    tile_floor: minimum tiles-per-device queue capacity; 0 = pairs_per_dev.
    """

    block_n: int = 0
    rerank_block: int = 0
    tile_floor: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "KernelGeometry":
        return cls(
            block_n=int(d.get("block_n", 0) or 0),
            rerank_block=int(d.get("rerank_block", 0) or 0),
            tile_floor=int(d.get("tile_floor", 0) or 0),
        )


def backend_info() -> tuple[str, str]:
    """(backend, device_kind) of the default jax backend (initializes jax)."""
    import jax

    return jax.default_backend(), jax.devices()[0].device_kind


def cache_path(backend: str, cache_dir: str | None = None) -> str:
    """Versioned per-backend user cache file (created on first sweep)."""
    base = cache_dir or os.path.join(
        os.path.expanduser("~"), ".cache", "repro"
    )
    return os.path.join(
        base, f"autotune-{backend}-v{CACHE_VERSION}.json"
    )


def defaults_path() -> str:
    """In-repo fallback table (`repro/configs/autotune_defaults.json`)."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "configs",
        "autotune_defaults.json",
    )


def load_cache(backend: str, cache_dir: str | None = None) -> dict:
    """Entries of the user cache; {} when absent, unreadable, or stale.

    Stale-version invalidation is double-guarded: the version is in the
    filename (an old build's cache is simply a different file) AND in the
    document (a hand-copied or future-versioned file is ignored rather
    than misapplied).
    """
    path = cache_path(backend, cache_dir)
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
    if not isinstance(doc, dict) or doc.get("version") != CACHE_VERSION:
        return {}
    entries = doc.get("entries")
    return entries if isinstance(entries, dict) else {}


def save_cache(
    backend: str, entries: dict, cache_dir: str | None = None
) -> str:
    """Merge `entries` into the user cache (atomic rewrite); returns path."""
    path = cache_path(backend, cache_dir)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    merged = load_cache(backend, cache_dir)
    merged.update(entries)
    doc = {"version": CACHE_VERSION, "backend": backend, "entries": merged}
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def load_defaults(backend: str) -> KernelGeometry | None:
    """Per-backend geometry from the in-repo defaults table (or None)."""
    try:
        with open(defaults_path()) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(doc, dict) or doc.get("version") != CACHE_VERSION:
        return None
    entry = (doc.get("backends") or {}).get(backend)
    if not isinstance(entry, dict):
        return None
    return KernelGeometry.from_dict(entry)


def _pow2(n: int) -> int:
    return 1 << math.ceil(math.log2(max(int(n), 1)))


def engine_key(engine, k: int, device_kind: str | None = None) -> str:
    """Cache key: (device kind, shard-shape bucket, k bucket).

    The shard-shape bucket covers everything that changes which executable
    family the scan runs: stored width and dtype, addressing mode
    (add_offsets), subspace count, and the pow2 per-device row-capacity
    bucket.  `k` is pow2-bucketed like the serving layer's fetch sizes.
    Two engines with the same key can safely share a tuned geometry.
    """
    if device_kind is None:
        _, device_kind = backend_info()
    s = engine.shards
    mode = "raw" if s.add_offsets else "addr"
    return (
        f"{device_kind}|w{s.width}x{s.codes.dtype.itemsize}{mode}"
        f"|m{s.m_subspaces}|cap{_pow2(s.codes.shape[1])}"
        f"|k{_pow2(max(k, 1))}|rerank-{engine.rerank}"
    )


# ------------------------------ sweeping ------------------------------- #


def _median_s(fn, iters: int, warmup: int) -> float:
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _time_scan(
    engine, block_n: int, k: int, iters: int, warmup: int
) -> float:
    """Median seconds for one tiles-scan over SWEEP_TILES synthetic tiles.

    The synthetic shard mirrors the real one in every executable-shaping
    way (width, storage dtype, table size, addressing mode, path), so the
    timed kernel is the one production dispatches — only the row contents
    and tile count are synthetic.
    """
    s = engine.shards
    rng = np.random.default_rng(0)
    rows = SWEEP_TILES * block_n
    if s.add_offsets:
        codes = rng.integers(0, 256, (rows, s.width), dtype=np.uint8)
    else:
        codes = rng.integers(0, s.sentinel, (rows, s.width)).astype(
            s.codes.dtype
        )
    tables = rng.standard_normal((1, s.table_size)).astype(np.float32)
    tile_pair = np.zeros(SWEEP_TILES, np.int32)
    tile_block = np.arange(SWEEP_TILES, dtype=np.int32)
    tile_row0 = (np.arange(SWEEP_TILES) * block_n).astype(np.int32)
    n_valid = np.asarray([rows], np.int32)

    def fn():
        return ops.adc_topk_tiles(
            tables, codes.T, tile_pair, tile_block, tile_row0, n_valid,
            max(k, 1),
            block_n=block_n, path=engine.path, add_offsets=s.add_offsets,
            interpret=engine.interpret,
        )

    return _median_s(fn, iters, warmup)


def _time_rerank(
    engine, block_k: int, k: int, iters: int, warmup: int
) -> float:
    """Median seconds for one re-rank kernel call at the cascade width."""
    dim = (
        engine.raw.dim
        if engine.raw is not None
        else engine.index.centroids.shape[1]
    )
    kp = engine.k_prime(max(k, 1))
    rng = np.random.default_rng(1)
    queries = rng.standard_normal((8, dim)).astype(np.float32)
    cand = rng.standard_normal((8, kp, dim)).astype(np.float32)

    def fn():
        return ops.rerank_dists(
            queries, cand, block_k=block_k, interpret=engine.interpret
        )

    return _median_s(fn, iters, warmup)


def sweep_engine(
    engine,
    k: int,
    block_ns: tuple[int, ...] | None = None,
    rerank_blocks: tuple[int, ...] | None = None,
    iters: int = 2,
    warmup: int = 1,
) -> tuple[KernelGeometry, dict]:
    """Time the candidate grid on synthetic shards; return (argmin, report).

    The engine's current `block_n` is always in the grid, so the swept
    pick can never be worse than the default on the measured workload
    (ties keep the smaller timing; an exact tie on the current geometry
    costs nothing — same executable).  The two knobs are independent
    (different kernels), so their argmins are taken independently.
    """
    s = engine.shards
    if block_ns is None:
        block_ns = tuple(sorted({s.block_n, *DEFAULT_BLOCK_NS}))
    else:
        block_ns = tuple(sorted({s.block_n, *block_ns}))
    scan_times = {
        bn: _time_scan(engine, bn, k, iters, warmup) for bn in block_ns
    }
    best_bn = min(scan_times, key=scan_times.get)

    rerank_times: dict[int, float] = {}
    best_bk = 0
    if engine.rerank == "exact":
        if rerank_blocks is None:
            kp2 = _pow2(engine.k_prime(max(k, 1)))
            rerank_blocks = tuple(sorted({ops.LANE, max(ops.LANE, kp2)}))
        rerank_times = {
            bk: _time_rerank(engine, bk, k, iters, warmup)
            for bk in rerank_blocks
        }
        best_bk = min(rerank_times, key=rerank_times.get)

    geo = KernelGeometry(
        block_n=int(best_bn),
        rerank_block=int(best_bk),
        tile_floor=int(engine.tile_floor),
    )
    report = {
        "swept": len(scan_times) + len(rerank_times),
        "scan_s": {str(bn): t for bn, t in scan_times.items()},
        "rerank_s": {str(bk): t for bk, t in rerank_times.items()},
    }
    return geo, report


# ------------------------------ entry point ---------------------------- #


def autotune_engine(
    engine,
    k: int,
    mode: str = "cache",
    cache_dir: str | None = None,
    block_ns: tuple[int, ...] | None = None,
    rerank_blocks: tuple[int, ...] | None = None,
) -> tuple[KernelGeometry | None, dict]:
    """Resolve the tuned geometry for (engine, k) under an autotune mode.

    Returns (geometry | None, report).  The report always carries `mode`,
    `source` ("off" | "cache" | "sweep" | "defaults" | "miss"), `swept`
    (candidates timed this call — 0 on every cache hit), the cache `key`,
    and the applied geometry.  Modes:

      "off"   : never touch the engine; (None, report).
      "cache" : apply the cached entry for this key if present, else the
                in-repo per-backend default, else nothing ("miss").
      "sweep" : like "cache" on a hit (the sweep already ran once for
                this key on this machine); on a miss, run `sweep_engine`
                and persist the winner, so the NEXT process start — and
                the second CI run — sweeps 0 candidates.
    """
    if mode not in ("off", "cache", "sweep"):
        raise ValueError(
            f"autotune must be 'off', 'cache' or 'sweep', got {mode!r}"
        )
    report: dict = {"mode": mode, "source": "off", "swept": 0}
    if mode == "off":
        return None, report
    backend, device_kind = backend_info()
    key = engine_key(engine, k, device_kind=device_kind)
    report.update(
        backend=backend, device_kind=device_kind, key=key,
        cache_path=cache_path(backend, cache_dir),
    )
    entries = load_cache(backend, cache_dir)
    entry = entries.get(key)
    if isinstance(entry, dict):
        geo = KernelGeometry.from_dict(entry)
        report.update(source="cache", geometry=geo.as_dict())
        return geo, report
    if mode == "sweep":
        geo, sweep_report = sweep_engine(
            engine, k, block_ns=block_ns, rerank_blocks=rerank_blocks
        )
        save_cache(
            backend,
            {key: {**geo.as_dict(), "timings": sweep_report}},
            cache_dir,
        )
        report.update(
            source="sweep", swept=sweep_report["swept"],
            geometry=geo.as_dict(), timings=sweep_report,
        )
        return geo, report
    geo = load_defaults(backend)
    if geo is not None:
        report.update(source="defaults", geometry=geo.as_dict())
        return geo, report
    report.update(source="miss")
    return None, report
