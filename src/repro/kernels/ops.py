"""Public jit'd wrappers for the Pallas kernels: padding, dtype widening,
block-size selection, backend dispatch (interpret=True off-TPU).

Tables are handed to the kernels at any width: `adc_scan.as_chunks` pads
them to a LANE multiple and views them as (A / 128, 128) chunk rows.

API (all return the same values as the matching ref.py oracle):
  adc_scan(lut, codes)                plain ADC distances
  adc_scan_flat(ext_lut, addrs)       direct-address ADC distances
  adc_topk(luts, codes, k)            fused scan + top-k (multi-query)
  adc_topk_flat(ext_luts, addrs, k)   ... over co-occ encoded codes
  adc_topk_pairs(tables, addrs, ...)  per-pair materialized windows
  adc_topk_windows(tables, codes_t,.) per-pair padded windows, shared codes
  adc_topk_tiles(tables, codes_t, .)  flat tile work queue, shared codes
  build_luts(codebook, qmc)           stage-(b) LUT construction (jnp)
  build_ext_luts(luts, cols, codes)   fused [LUT | combo sums | 0] tables
  rerank_dists(queries, cand)         exact f32 re-rank distances (cascade)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import lut as _core_lut
from repro.kernels import adc_scan as _scan
from repro.kernels import adc_topk as _topk
from repro.kernels import lut_build as _lut
from repro.kernels import rerank as _rerank

NCODES = 256
LANE = _scan.LANE  # TPU lane width: pad tables/blocks to multiples of this


def interpret_mode(interpret: bool | None = None) -> bool:
    """Pallas interpret mode: as given, else on every backend but TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _round_up(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


def _codes_to_addrs(codes: jax.Array) -> jax.Array:
    """(N, M) uint8 codes -> (N, M) int32 flat addresses col*256 + code."""
    m = codes.shape[-1]
    offs = (jnp.arange(m, dtype=jnp.int32) * NCODES)[None, :]
    return codes.astype(jnp.int32) + offs


def _pad_rows(addrs: jax.Array, block_n: int, fill: int) -> jax.Array:
    n = addrs.shape[0]
    pad = _round_up(max(n, block_n), block_n) - n
    if pad == 0:
        return addrs
    return jnp.pad(addrs, ((0, pad), (0, 0)), constant_values=fill)


@functools.partial(
    jax.jit, static_argnames=("block_n", "path", "interpret")
)
def adc_scan(
    lut: jax.Array,
    codes: jax.Array,
    *,
    block_n: int = 1024,
    path: str = "gather",
    interpret: bool | None = None,
) -> jax.Array:
    """(M, 256) x (N, M) -> (N,) ADC distances via the Pallas kernel."""
    interpret = interpret_mode(interpret)
    n = codes.shape[0]
    table = lut.reshape(-1)
    addrs = _pad_rows(_codes_to_addrs(codes), block_n, fill=0)
    out = _scan.adc_scan_kernel(
        table, addrs, block_n=block_n, path=path, interpret=interpret
    )
    return out[:n]


@functools.partial(
    jax.jit, static_argnames=("block_n", "path", "interpret")
)
def adc_scan_flat(
    ext_lut: jax.Array,
    addrs: jax.Array,
    *,
    block_n: int = 1024,
    path: str = "gather",
    interpret: bool | None = None,
) -> jax.Array:
    """(A,) x (N, W) direct-address scan -> (N,)."""
    interpret = interpret_mode(interpret)
    n = addrs.shape[0]
    # pad rows with the zero-sentinel address (A-1 of the unpadded table)
    sentinel = ext_lut.shape[-1] - 1
    addrs_p = _pad_rows(addrs.astype(jnp.int32), block_n, fill=sentinel)
    out = _scan.adc_scan_kernel(
        ext_lut, addrs_p, block_n=block_n, path=path, interpret=interpret
    )
    return out[:n]


@functools.partial(
    jax.jit, static_argnames=("k", "block_n", "path", "interpret")
)
def adc_topk(
    luts: jax.Array,
    codes: jax.Array,
    k: int,
    *,
    block_n: int = 1024,
    path: str = "gather",
    interpret: bool | None = None,
    bound: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(Q, M, 256) x (N, M) -> ((Q, k) dists, (Q, k) idx), fused.

    `bound` is an optional (Q,) f32 per-query warm-start bound (a STRICT
    upper bound on the final k-th distance; see adc_topk.py)."""
    interpret = interpret_mode(interpret)
    q = luts.shape[0]
    n = codes.shape[0]
    tables = luts.reshape(q, -1)
    addrs = _pad_rows(_codes_to_addrs(codes), block_n, fill=0)
    n_valid = jnp.asarray([n], jnp.int32)
    return _topk.adc_topk_kernel(
        tables,
        addrs,
        n_valid,
        k=k,
        block_n=block_n,
        path=path,
        interpret=interpret,
        bound=bound,
    )


@functools.partial(
    jax.jit, static_argnames=("k", "block_n", "path", "interpret")
)
def adc_topk_flat(
    ext_luts: jax.Array,
    addrs: jax.Array,
    k: int,
    *,
    block_n: int = 1024,
    path: str = "gather",
    interpret: bool | None = None,
    bound: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(Q, A) x (N, W) direct-address fused scan + top-k."""
    interpret = interpret_mode(interpret)
    n = addrs.shape[0]
    sentinel = ext_luts.shape[-1] - 1
    addrs_p = _pad_rows(addrs.astype(jnp.int32), block_n, fill=sentinel)
    n_valid = jnp.asarray([n], jnp.int32)
    return _topk.adc_topk_kernel(
        ext_luts,
        addrs_p,
        n_valid,
        k=k,
        block_n=block_n,
        path=path,
        interpret=interpret,
        bound=bound,
    )


@functools.partial(
    jax.jit, static_argnames=("k", "block_n", "path", "interpret")
)
def adc_topk_pairs(
    tables: jax.Array,
    addrs: jax.Array,
    n_valid: jax.Array,
    k: int,
    *,
    block_n: int = 1024,
    path: str = "gather",
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-pair fused scan+top-k: tables (P, A), addrs (P, L, W) int32
    (already flat/direct addresses), n_valid (P,).  L must be a block_n
    multiple (the retrieval layout aligns cluster slots)."""
    interpret = interpret_mode(interpret)
    return _topk.adc_topk_pairs_kernel(
        tables,
        addrs.astype(jnp.int32),
        n_valid.astype(jnp.int32),
        k=k,
        block_n=block_n,
        path=path,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "window", "block_n", "path", "add_offsets", "interpret",
        "n_queries", "with_stats",
    ),
)
def adc_topk_windows(
    tables: jax.Array,
    codes_t: jax.Array,
    starts: jax.Array,
    n_valid: jax.Array,
    k: int,
    *,
    window: int,
    block_n: int = 1024,
    path: str = "gather",
    add_offsets: bool = False,
    interpret: bool | None = None,
    pair_q: jax.Array | None = None,
    pair_lb: jax.Array | None = None,
    bound: jax.Array | None = None,
    n_queries: int = 1,
    with_stats: bool = False,
):
    """Per-pair window scan over a shared device-resident code array.

    tables (P, A); codes_t (W, cap) column-major flat addresses (uint8 raw
    codes when add_offsets -- widened in VMEM, so HBM sees the compact
    dtype); starts
    (P,) block_n-aligned row starts; n_valid (P,).  The production path:
    windows are indexed via scalar prefetch, never materialized.

    `pair_q`/`pair_lb`/`bound` drive the early-pruning-v2 whole-tile skip
    (see adc_topk.py); the defaults reproduce the unpruned scan exactly.
    With `with_stats=True` additionally returns the (P, 3) int32
    [tiles skipped, rows avoided, rows merged] counters.
    """
    interpret = interpret_mode(interpret)
    start_blocks = starts.astype(jnp.int32) // block_n
    vals, idx, stats = _topk.adc_topk_windows_kernel(
        tables,
        codes_t,
        start_blocks,
        n_valid.astype(jnp.int32),
        k=k,
        window=window,
        block_n=block_n,
        path=path,
        add_offsets=add_offsets,
        interpret=interpret,
        pair_q=pair_q,
        pair_lb=pair_lb,
        bound=bound,
        n_queries=n_queries,
    )
    if with_stats:
        return vals, idx, stats
    return vals, idx


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "block_n", "path", "add_offsets", "interpret", "n_queries",
        "with_stats",
    ),
)
def adc_topk_tiles(
    tables: jax.Array,
    codes_t: jax.Array,
    tile_pair: jax.Array,
    tile_block: jax.Array,
    tile_row0: jax.Array,
    n_valid: jax.Array,
    k: int,
    *,
    block_n: int = 1024,
    path: str = "gather",
    add_offsets: bool = False,
    interpret: bool | None = None,
    pair_q: jax.Array | None = None,
    pair_lb: jax.Array | None = None,
    bound: jax.Array | None = None,
    n_queries: int = 1,
    with_stats: bool = False,
):
    """Flat work-queue scan over a shared device-resident code array.

    tables (P, A); codes_t (W, cap) column-major (raw uint8 when
    add_offsets); tile_pair / tile_block / tile_row0 (T,) int32 work items
    from `emit_tiles` (pair id P marks dummy padding tiles); n_valid (P,).
    One grid step per REAL code tile -- device wall-clock is sum(actual
    probed rows), not P * max-cluster window.

    `pair_q`/`pair_lb`/`bound` drive the early-pruning-v2 whole-tile skip
    (see adc_topk.py); the defaults reproduce the unpruned scan exactly.
    With `with_stats=True` additionally returns the (P, 3) int32
    [tiles skipped, rows avoided, rows merged] counters.
    """
    interpret = interpret_mode(interpret)
    vals, idx, stats = _topk.adc_topk_tiles_kernel(
        tables,
        codes_t,
        tile_pair.astype(jnp.int32),
        tile_block.astype(jnp.int32),
        tile_row0.astype(jnp.int32),
        n_valid.astype(jnp.int32),
        k=k,
        block_n=block_n,
        path=path,
        add_offsets=add_offsets,
        interpret=interpret,
        pair_q=pair_q,
        pair_lb=pair_lb,
        bound=bound,
        n_queries=n_queries,
    )
    if with_stats:
        return vals, idx, stats
    return vals, idx


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def rerank_dists(
    queries: jax.Array,
    cand: jax.Array,
    *,
    block_k: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Exact re-rank distances: (Q, D) x (Q, K, D) -> (Q, K) f32 sq-L2.

    Second cascade stage: `cand` holds the raw vectors of the ADC scan's
    overfetched candidates, gathered by candidate id (rows of invalid
    candidates may hold arbitrary finite data -- callers mask their
    distances out afterwards, see retrieval.search.sharded_rerank).  The
    candidate axis K is padded to a `block_k` multiple (default LANE) for
    the kernel and sliced back, so any pow2 candidate bucket maps onto an
    aligned block; `block_k` is the candidate-block width per grid step
    (the autotuned re-rank geometry knob -- results are bit-identical at
    every value, see rerank_dists_kernel).  Storage dtype may be f32 or
    bf16; sums are always f32.
    """
    interpret = interpret_mode(interpret)
    bk = block_k or LANE
    k = cand.shape[1]
    kpad = _round_up(k, bk) - k
    if kpad:
        cand = jnp.pad(cand, ((0, 0), (0, kpad), (0, 0)))
    out = _rerank.rerank_dists_kernel(
        queries.astype(jnp.float32), cand, block_k=bk, interpret=interpret
    )
    return out[:, :k]


@jax.jit
def build_luts(codebook: jax.Array, qmc: jax.Array) -> jax.Array:
    """(M, 256, dsub) x (Q, M, dsub) -> (Q, M, 256).

    A plain fused XLA reduction on every backend (`core.lut.build_luts`):
    the LUT build is elementwise work with a short reduction, which XLA
    already streams at HBM rate."""
    q = qmc.shape[0]
    return _core_lut.build_luts(codebook, qmc.reshape(q, -1))


@functools.partial(jax.jit, static_argnames=("interpret",))
def build_ext_luts(
    luts: jax.Array,
    combo_cols: jax.Array,
    combo_codes: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused extended tables: (Q, M, 256) + (m, L) combos -> (Q, A).

    A = M*256 + n_combos + 1 exactly (the sentinel is the last slot); the
    scan kernels pad tables to a LANE multiple themselves.
    """
    interpret = interpret_mode(interpret)
    q, m, _ = luts.shape
    n_combos = combo_cols.shape[0]
    caddr = combo_cols.astype(jnp.int32) * NCODES + combo_codes.astype(
        jnp.int32
    )
    a = m * NCODES + n_combos + 1
    out = _lut.ext_lut_kernel(
        luts, caddr, t_pad=_round_up(a, LANE), interpret=interpret
    )
    return out[:, :a]
