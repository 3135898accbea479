"""Pallas TPU kernel: fused ADC scan + running top-k with early pruning.

This is the TPU adaptation of paper §4.2 (thread pipeline) + §4.4 (top-k
pruning): instead of thread-local heaps merged through semaphores, each grid
step scans one (W, block_n) tile of codes and folds it into a k-sized running
result held in VMEM.  The paper's pruning rule survives, applied per row:
a row whose distance is not below the current k-th best is never merged,
so a tile whose minimum is not below it runs no merge round -- exactly
"the remaining values cannot contribute to the overall top-k and can
therefore be pruned".

Grid is (Q, num_tiles): the LUT of query q stays resident in VMEM while its
tiles stream -- one query's scan is the paper's "single cluster processed by
all threads"; multiple queries iterate in the outer grid dimension, matching
the sequential cluster loop on a DPU.

Early pruning v2 -- whole-tile skips and warm-start bounds
----------------------------------------------------------
The production kernels (tiles / windows) additionally accept host-computed
bounds that let them skip the *entire* tile body (gather / one-hot distance
computation included), not just the merge, while staying bit-identical to
the unpruned scan.  The soundness argument, which the equivalence test wall
(`tests/test_pruning_props.py`) pins empirically:

* **Per-pair lower bound** ``lb(q, c)``.  Every ADC distance in pair
  (q, c)'s window is ``sum_m lut[m, code_m]`` with
  ``lut[m, j] = ||r_m - cb[m, j]||^2`` built from the residual
  ``r = q - centroid_c``.  By the reverse triangle inequality per subspace,
  ``lut[m, j] >= max(0, ||r_m|| - R_m)^2`` where ``R_m`` is the largest
  codeword norm of codebook m, so
  ``lb = sum_m max(0, ||r_m|| - R_m)^2`` lower-bounds every distance the
  scan can produce for that pair.  The host deflates it by a relative +
  absolute margin (`core.scheduling.residual_bounds`) that dominates the
  f32 rounding of both the on-device LUT build and the gather-sum, so the
  deflated bound is <= every f32 distance the kernel computes.

* **Warm-start bound ``b0(q)``** (a *strict* upper bound on the query's
  final k-th output distance).  Symmetrically, every row of cluster c has
  ADC distance <= ``ub(q, c) = sum_m (||r_m|| + R_m)^2``.  Accumulating the
  probed clusters' sizes in ascending-``ub`` order until >= k rows are
  covered yields a value V such that at least k candidates have distance
  <= V, hence the final k-th <= V.  The host *inflates* V past every f32
  rounding source, so ``b0 > final k-th`` strictly -- any row dropped
  because it sits above ``b0`` is strictly beyond the output cut.

* **Running per-query bound ``sq(q)``**.  After any pair of query q has
  merged k candidates, its current k-th value upper-bounds the query's
  *global* k-th (k real candidates exist at or below it), so the kernels
  keep ``sq[q] = min`` over the pair k-th values seen so far and tighten
  the warm start as the scan proceeds.  Best-first tile ordering
  (`core.scheduling.emit_tiles(pair_key=...)`) visits low-``lb`` pairs
  first so this happens within the first few tiles.

* **Skip rule**: a tile's body is skipped iff ``lb >= pair_kth`` (the merge
  would be a no-op -- the original §4.4 rule with the sound lower bound in
  place of the computed tile min) **or** ``lb > min(b0, sq)`` (every row in
  the tile is strictly beyond the final k-th).  Dropped rows are therefore
  strictly greater than the final k-th output value, so the <=-k-th prefix
  of every per-pair ascending result list is unchanged and sits at the same
  lanes; every downstream merge (per-query local, cross-device global) sees
  the same candidates at the same positions, and the output is bit-identical
  -- distances *and* ids, ties included.

* **Per-row admission**: the same argument, row by row, inside a scanned
  tile.  A row is merged iff ``d < pair_kth`` (strict: a running entry
  wins a tie, so the row could not enter) and ``d <= qbound`` with
  ``qbound = min(b0, sq)`` (non-strict, so ties at the final k-th
  survive).  A dropped row is strictly beyond the final k-th.  Every row
  at or below it is admitted, and so is every row that precedes one in
  stable (value, position) order, so each keeps its position in the
  pair's list.  A pair's list is thus the k smallest of its *admitted*
  rows: it agrees with the unfiltered list on every entry at or below the
  current ``sq``, and past it may hold other entries or (+inf, -1) tails,
  as a pair that emits no tiles already returns.  Its k-th is never below
  the unfiltered list's, and differs only where that one already exceeds
  ``sq``; then ``min(sq, k-th)`` is ``sq`` on both sides, so ``sq``'s
  trajectory is unchanged, and so is every skip decision: where the k-ths
  differ, ``qbound < unfiltered k-th <= filtered k-th``, so a tile with
  ``lb > qbound`` skips on both sides and one with ``lb <= qbound`` lies
  below both k-ths (``lb >= pair_kth`` is false on both).  The flat
  kernel's ``qbound`` is the caller's strict bound; the pairs kernel's is
  +inf, and admission reduces to the pair's k-th.

The per-tile merge (`merge_admitted`) inserts the admitted rows one per
round: the smallest admitted (value, lane) takes two tile reductions, its
id is ``row0 + lane`` (tile ids are affine in the lane), and it lands after
every running entry ``<=`` its value -- running entries and earlier
insertions first, the order of a stable ascending sort -- while the tail
shifts one lane right and the k-th drops off.  Rounds stop once the
smallest admitted value is not below the row's current k-th, so a pair
pays about k·ln(N/k) insertions over its N rows in scan order, where a
re-selection of the whole row would pay k rounds on every merging tile.
The insertions are the kernels' third stats lane (rows merged);
`ref.merge_topk_rounds_ref` keeps the k-round re-selection as the oracle.

TPU layout (what Mosaic accepts): tables arrive as (A / 128, 128) chunk
blocks and code tiles are scanned transposed (`adc_scan.tile_dists`); the
running top-k of the pair being scanned lives in its (1, k) output block,
which stays in VMEM while consecutive grid steps revisit it; per-query
bounds live in SMEM.  The tile queue is scanned in chunks of at most
`TILE_CHUNK` tiles so its scalar-prefetched metadata fits in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.adc_scan import as_chunks, tile_dists

NEG_INF = float("-inf")
BIG = jnp.iinfo(jnp.int32).max
# tiles per pallas_call of the tile-list scan: its six scalar-prefetched
# (chunk,) int32/f32 arrays take 6 * 4 * TILE_CHUNK bytes of the 1 MiB SMEM
TILE_CHUNK = 8192


def tile_queue_chunks(t_n: int) -> tuple[int, int]:
    """(chunk, n_chunks) the tiles scan splits a queue of `t_n` tiles into.

    The scan runs one grid step per tile of `n_chunks * chunk`: the queue
    is padded with dummy tiles to whole chunks."""
    chunk = max(1, min(t_n, TILE_CHUNK))
    return chunk, -(-t_n // chunk)


def merge_admitted(
    cur_v: jax.Array,
    cur_i: jax.Array,
    dists: jax.Array,
    row0: jax.Array,
    kth: jax.Array,
    qbound: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Insert the tile rows that can reach the answer into the running top-k.

    cur_v / cur_i: (1, k) running top-k, ascending, with `kth` its last
    value; dists: (1, BN) tile distances (+inf past the valid rows), whose
    row `lane` has id `row0 + lane`.  A row is admitted iff
    `d < kth` (a running entry wins a tie) and `d <= qbound`.  Each round
    takes the smallest admitted (value, lane) and places it after every
    entry <= its value, so the row stays in stable (value, position)
    order; rounds stop once the smallest admitted value is not below the
    row's current k-th.  Returns (vals, idx, rows merged).
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, dists.shape, 1)
    pos = jax.lax.broadcasted_iota(jnp.int32, cur_v.shape, 1)
    cand = jnp.where((dists < kth) & (dists <= qbound), dists, jnp.inf)

    def below_kth(carry):
        _, _, _, m, kth, _ = carry
        return m < kth

    def insert(carry):
        v, i, cand, m, _, n = carry
        pick = jnp.min(jnp.where(cand == m, lane, BIG))
        # entries <= m keep their lane, the rest move one lane right and
        # the k-th drops off; what a roll wraps into lane 0 is overridden
        keep = v <= m
        sv = pltpu.roll(v, 1, 1)
        si = pltpu.roll(i, 1, 1)
        at = (pos == 0) | (sv <= m)
        v = jnp.where(keep, v, jnp.where(at, m, sv))
        i = jnp.where(keep, i, jnp.where(at, row0 + pick, si))
        cand = jnp.where(lane == pick, jnp.inf, cand)
        return v, i, cand, jnp.min(cand), jnp.max(v), n + 1

    v, i, _, _, _, n = jax.lax.while_loop(
        below_kth, insert,
        (cur_v, cur_i, cand, jnp.min(cand), kth, jnp.int32(0)),
    )
    return v, i, n


def _scan_and_merge(
    tab_ref, codes_t, v_ref, i_ref, row0, rows, kth, qbound, *,
    path: str, add_offsets: bool,
):
    """Distances of one code tile, merged into the (1, k) running top-k;
    returns the rows merged.

    Rows at or past `rows` are padding (+inf).  Only rows below the
    current k-th and at or below the query bound are merged (module
    docstring)."""
    dists = tile_dists(tab_ref, codes_t, path=path, add_offsets=add_offsets)
    lane = jax.lax.broadcasted_iota(jnp.int32, dists.shape, 1)
    dists = jnp.where(lane < rows, dists, jnp.inf)
    v, i, n = merge_admitted(
        v_ref[...], i_ref[...], dists, row0, kth, qbound
    )
    v_ref[...] = v
    i_ref[...] = i
    return n


def _add_stats(s_ref, skipped, avoided, merged):
    """Add to a pair's (1, 3) [tiles skipped, rows avoided, rows merged]
    counters."""
    lane = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    s_ref[...] = s_ref[...] + jnp.where(
        lane == 0, skipped, jnp.where(lane == 1, avoided, merged)
    )


def _adc_topk_kernel(
    nvalid_ref,  # scalar-prefetch: (1,) int32 valid rows
    bound_ref,   # scalar-prefetch: (Q,) f32 per-query strict upper bound
    table_ref,   # (C, 128) table of query q
    addr_ref,    # (W, block_n) flat addresses
    vals_out,    # (1, k) running top-k of query q
    idx_out,
    *,
    block_n: int,
    path: str,
):
    q = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        vals_out[...] = jnp.full(vals_out.shape, jnp.inf, vals_out.dtype)
        idx_out[...] = jnp.full(idx_out.shape, -1, jnp.int32)

    # §4.4 early pruning: merge only the rows that beat the current k-th
    # best and the caller's per-query bound (a strict upper bound on the
    # final k-th, so dropped rows can never appear in the output).
    rows = jnp.clip(nvalid_ref[0] - t * block_n, 0, block_n)
    _scan_and_merge(
        table_ref, addr_ref[...], vals_out, idx_out, t * block_n, rows,
        jnp.max(vals_out[...]), bound_ref[q], path=path, add_offsets=False,
    )


def _adc_topk_pairs_kernel(
    nvalid_ref,  # scalar-prefetch: (P,) int32 valid rows per pair
    table_ref,
    addr_ref,    # (W, block_n) tile of pair p's own window
    vals_out,
    idx_out,
    *,
    block_n: int,
    path: str,
):
    """Per-pair variant: pair p scans its *own* code window addr[p]."""
    p = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        vals_out[...] = jnp.full(vals_out.shape, jnp.inf, vals_out.dtype)
        idx_out[...] = jnp.full(idx_out.shape, -1, jnp.int32)

    rows = jnp.clip(nvalid_ref[p] - t * block_n, 0, block_n)
    _scan_and_merge(
        table_ref, addr_ref[...], vals_out, idx_out, t * block_n, rows,
        jnp.max(vals_out[...]), jnp.inf, path=path, add_offsets=False,
    )


def _adc_topk_tiles_kernel(
    prev_ref,    # scalar-prefetch: (1,) int32 pair of the tile before chunk
    tp_ref,      # scalar-prefetch: (C,) int32 pair per tile (P = dummy)
    tb_ref,      # scalar-prefetch: (C,) int32 code-block index per tile
    tr_ref,      # scalar-prefetch: (C,) int32 window row of the first row
    tn_ref,      # scalar-prefetch: (C,) int32 valid rows in the tile
    tq_ref,      # scalar-prefetch: (C,) int32 query of the tile's pair
    tlb_ref,     # scalar-prefetch: (C,) f32 lower bound of the tile's pair
    table_ref,   # (A/128, 128) table of this tile's pair
    codes_ref,   # (W, block_n) code tile
    v_in,        # (1, k) rows of the chunk's first pair, carried over
    i_in,        #   from the previous chunk when that pair straddles it
    s_in,
    sq_in,       # (Q,) f32 SMEM running per-query bounds
    vals_out,    # (1, k) running top-k of this tile's pair
    idx_out,
    stats_out,   # (1, 3) int32 [tiles skipped, rows avoided, rows merged]
    sq_out,      # (Q,) f32 SMEM running per-query bounds
    *,
    path: str,
    add_offsets: bool,
):
    """Tile-list variant (beyond-paper §Perf optimization): the host emits
    one work item per REAL code block, so no padded-window DMA at all.

    Tiles of one pair are contiguous in the work list (emit_tiles keeps
    each pair's run contiguous, ascending rows -- best-first ordering
    permutes whole runs only), so the pair's (1, k) output block stays in
    VMEM across its run and holds the running top-k; it is reset where a
    new pair's run starts and reloaded from the carried rows where a run
    straddles two chunks.

    Early-pruning v2: the whole tile body -- distance computation included
    -- sits behind the bound check (see the module docstring for the
    soundness argument), so a pruned tile costs a few scalar compares
    instead of a (block_n, W) scan.  Dummy tiles carry lb = +inf and prune
    away on the first condition.  `sq` starts at the caller's warm-start
    bound and tightens with every pair's k-th as the scan proceeds.

    This is Algorithm 2 pushed down to tile granularity: the same idea the
    paper uses to balance DPUs, reused to keep every DMA useful."""
    t = pl.program_id(0)
    pair = tp_ref[t]
    prev = jnp.where(t == 0, prev_ref[0], tp_ref[jnp.maximum(t - 1, 0)])

    @pl.when(t == 0)
    def _load_bounds():
        def copy(i, carry):
            sq_out[i] = sq_in[i]
            return carry

        jax.lax.fori_loop(0, sq_in.shape[0], copy, 0)

    @pl.when(pair != prev)
    def _fresh():
        vals_out[...] = jnp.full(vals_out.shape, jnp.inf, vals_out.dtype)
        idx_out[...] = jnp.full(idx_out.shape, -1, jnp.int32)
        stats_out[...] = jnp.zeros(stats_out.shape, jnp.int32)

    @pl.when((pair == prev) & (t == 0))
    def _carry():
        vals_out[...] = v_in[...]
        idx_out[...] = i_in[...]
        stats_out[...] = s_in[...]

    qi = tq_ref[t]
    lb = tlb_ref[t]
    rows = tn_ref[t]
    kth = jnp.max(vals_out[...])  # the row is kept sorted ascending
    qbound = sq_out[qi]
    # skip the whole tile body when the merge would provably be a no-op
    # (lb >= pair k-th) or every row is strictly past the final k-th
    # (lb > warm-start / running query bound)
    skip = (lb >= kth) | (lb > qbound)

    @pl.when(skip)
    def _account():
        _add_stats(stats_out, (rows > 0).astype(jnp.int32), rows, 0)

    @pl.when(jnp.logical_not(skip))
    def _scan():
        _add_stats(stats_out, 0, 0, _scan_and_merge(
            table_ref, codes_ref[...], vals_out, idx_out, tr_ref[t], rows,
            kth, qbound, path=path, add_offsets=add_offsets,
        ))

    # tighten the running query bound with this pair's (post-merge) k-th
    sq_out[qi] = jnp.minimum(sq_out[qi], jnp.max(vals_out[...]))


def _tiles_chunk_call(
    prev, tp, tb, tr, tn, tq, tlb, tables, codes_t, vals, idx, stats, sq, *,
    k: int, block_n: int, path: str, add_offsets: bool, interpret: bool,
):
    """One pallas_call over a chunk of the tile queue; the running state
    (vals, idx, stats, sq) is aliased in place."""
    n_pairs, n_chunks, _ = tables.shape
    w = codes_t.shape[0]

    def imap_pair(t, prev, tp, *_):
        return (jnp.minimum(tp[t], n_pairs - 1), 0, 0)

    def imap_codes(t, prev, tp, tb, *_):
        return (0, tb[t])

    def imap_out(t, prev, tp, *_):
        return (tp[t], 0, 0)

    def imap_first(t, prev, tp, *_):
        return (tp[0], 0, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(tp.shape[0],),
        in_specs=[
            pl.BlockSpec((None, n_chunks, 128), imap_pair),
            pl.BlockSpec((w, block_n), imap_codes),
            pl.BlockSpec((None, 1, k), imap_first),
            pl.BlockSpec((None, 1, k), imap_first),
            pl.BlockSpec((None, 1, 3), imap_first),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((None, 1, k), imap_out),
            pl.BlockSpec((None, 1, k), imap_out),
            pl.BlockSpec((None, 1, 3), imap_out),
            smem,
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _adc_topk_tiles_kernel, path=path, add_offsets=add_offsets
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(vals.shape, vals.dtype),
            jax.ShapeDtypeStruct(idx.shape, jnp.int32),
            jax.ShapeDtypeStruct(stats.shape, jnp.int32),
            jax.ShapeDtypeStruct(sq.shape, jnp.float32),
        ],
        # operands: 7 scalar-prefetch, tables, codes_t, vals, idx, stats, sq
        input_output_aliases={9: 0, 10: 1, 11: 2, 12: 3},
        interpret=interpret,
    )(prev, tp, tb, tr, tn, tq, tlb, tables, codes_t, vals, idx, stats, sq)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "block_n", "path", "interpret", "add_offsets", "n_queries",
    ),
)
def adc_topk_tiles_kernel(
    tables: jax.Array,       # (P, A)
    codes_t: jax.Array,      # (W, cap) int32/uint16/uint8 device-resident
    tile_pair: jax.Array,    # (T,) int32 (== P for dummy/padding tiles)
    tile_block: jax.Array,   # (T,) int32 code block index
    tile_row0: jax.Array,    # (T,) int32 window-relative first row
    n_valid: jax.Array,      # (P,) int32
    *,
    k: int,
    block_n: int = 1024,
    path: str = "gather",
    add_offsets: bool = False,
    interpret: bool = False,
    pair_q: jax.Array | None = None,    # (P,) int32 query per pair
    pair_lb: jax.Array | None = None,   # (P,) f32 pair lower bounds
    bound: jax.Array | None = None,     # (n_queries,) f32 warm-start bounds
    n_queries: int = 1,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Flat work-queue fused scan+top-k: one grid step per REAL code tile.

    tile_pair must keep each pair's tiles contiguous (ascending rows within
    the run) as produced by `emit_tiles` -- best-first ordering permutes
    whole runs, never splits them.  Pairs that emitted no tiles
    (n_valid == 0) come back as (inf, -1) rows with zero stats.

    `pair_lb` / `bound` enable whole-tile pruning (module docstring); the
    defaults (-inf / +inf) reproduce the unpruned scan bit-for-bit.  Returns
    ((P, k) dists, (P, k) idx, (P, 3) int32 [tiles skipped, rows avoided,
    rows merged]).  Rows merged counts the insertions into the pair's
    running top-k.
    """
    p = tables.shape[0]
    t_n = tile_pair.shape[0]
    assert codes_t.shape[1] % block_n == 0
    if pair_q is None:
        # one virtual query per pair: the running query bound degenerates
        # to the pair's own k-th, i.e. exactly the legacy (uncoupled) scan
        pair_q = jax.lax.iota(jnp.int32, p)
        n_queries = p
        bound = None
    if pair_lb is None:
        pair_lb = jnp.full((p,), NEG_INF, jnp.float32)
    if bound is None:
        bound = jnp.full((n_queries,), jnp.inf, jnp.float32)
    # pad the queue to whole chunks with dummy tiles (pair P: no valid
    # rows, lb = +inf -> they always prune away)
    chunk, n_chunks = tile_queue_chunks(t_n)
    pad = n_chunks * chunk - t_n

    def padded(x, fill):
        return jnp.pad(x.astype(jnp.int32), (0, pad), constant_values=fill)

    tp = padded(tile_pair, p)
    tb = padded(tile_block, 0)
    tr = padded(tile_row0, 0)
    # per-tile copies of the per-pair scalars (row P: the dummy pair)
    nvalid_ext = jnp.append(n_valid.astype(jnp.int32), 0)
    pair_q_ext = jnp.append(pair_q.astype(jnp.int32), 0)
    pair_lb_ext = jnp.append(pair_lb.astype(jnp.float32), jnp.inf)
    tn = jnp.clip(nvalid_ext[tp] - tr, 0, block_n)
    tq = pair_q_ext[tp]
    tlb = pair_lb_ext[tp]
    # pair of the tile just before each chunk (-1 before the first)
    prev = jnp.concatenate(
        [jnp.full((1,), -1, jnp.int32), tp[chunk - 1:-1:chunk]]
    )
    state = (
        jnp.full((p + 1, 1, k), jnp.inf, tables.dtype),
        jnp.full((p + 1, 1, k), -1, jnp.int32),
        jnp.zeros((p + 1, 1, 3), jnp.int32),
        bound.astype(jnp.float32),
    )
    chunks = as_chunks(tables)
    call = functools.partial(
        _tiles_chunk_call, k=k, block_n=block_n, path=path,
        add_offsets=add_offsets, interpret=interpret,
    )

    def body(c, state):
        def sl(x):
            return jax.lax.dynamic_slice_in_dim(x, c * chunk, chunk)

        return tuple(call(
            jax.lax.dynamic_slice_in_dim(prev, c, 1), sl(tp), sl(tb),
            sl(tr), sl(tn), sl(tq), sl(tlb), chunks, codes_t, *state,
        ))

    vals, idx, stats, _ = jax.lax.fori_loop(0, n_chunks, body, state)
    return vals[:p, 0], idx[:p, 0], stats[:p, 0]


def _adc_topk_windows_kernel(
    start_blk_ref,   # scalar-prefetch: (P,) int32 window start (in blocks)
    nvalid_ref,      # scalar-prefetch: (P,) int32 valid rows per window
    pair_q_ref,      # scalar-prefetch: (P,) int32 query index per pair
    pair_lb_ref,     # scalar-prefetch: (P,) f32 pair distance lower bound
    bound_ref,       # (Q,) f32 SMEM per-query warm-start bound
    table_ref,       # (A/128, 128) table of pair p
    codes_ref,       # (W, block_n) tile selected by the prefetched index map
    vals_out,        # (1, k) running top-k of pair p
    idx_out,
    stats_out,       # (1, 3) int32 [skipped, avoided, merged] of pair p
    sq,              # (Q,) f32 SMEM running per-query bound
    *,
    block_n: int,
    path: str,
    add_offsets: bool = False,
):
    """Window variant: pair p scans tiles [start[p], start[p] + T) of the
    device-resident code array -- no window materialization.  This is the
    HBM->VMEM streaming loop of the DPU (MRAM->WRAM DMA), with the §4.4
    pruning applied per tile and the early-pruning-v2 bounds (module
    docstring) skipping whole tile bodies."""
    del start_blk_ref  # consumed by the BlockSpec index_map
    p = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when((p == 0) & (t == 0))
    def _init_query():
        def copy(i, carry):
            sq[i] = bound_ref[i]
            return carry

        jax.lax.fori_loop(0, sq.shape[0], copy, 0)

    @pl.when(t == 0)
    def _init():
        vals_out[...] = jnp.full(vals_out.shape, jnp.inf, vals_out.dtype)
        idx_out[...] = jnp.full(idx_out.shape, -1, jnp.int32)
        stats_out[...] = jnp.zeros(stats_out.shape, jnp.int32)

    qi = pair_q_ref[p]
    lb = pair_lb_ref[p]
    rows = jnp.clip(nvalid_ref[p] - t * block_n, 0, block_n)
    kth = jnp.max(vals_out[...])
    qbound = sq[qi]
    skip = (lb >= kth) | (lb > qbound)

    @pl.when(skip)
    def _account():
        _add_stats(stats_out, (rows > 0).astype(jnp.int32), rows, 0)

    @pl.when(jnp.logical_not(skip))
    def _scan():
        _add_stats(stats_out, 0, 0, _scan_and_merge(
            table_ref, codes_ref[...], vals_out, idx_out, t * block_n, rows,
            kth, qbound, path=path, add_offsets=add_offsets,
        ))

    sq[qi] = jnp.minimum(sq[qi], jnp.max(vals_out[...]))


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "window", "block_n", "path", "interpret", "add_offsets",
        "n_queries",
    ),
)
def adc_topk_windows_kernel(
    tables: jax.Array,
    codes_t: jax.Array,
    start_blocks: jax.Array,
    n_valid: jax.Array,
    *,
    k: int,
    window: int,
    block_n: int = 1024,
    path: str = "gather",
    add_offsets: bool = False,
    interpret: bool = False,
    pair_q: jax.Array | None = None,
    pair_lb: jax.Array | None = None,
    bound: jax.Array | None = None,
    n_queries: int = 1,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused scan + top-k over per-pair windows of a shared code array.

    Args:
      tables: (P, A) float32 flat tables.
      codes_t: (W, cap) device-resident flat addresses, column-major
        (block-aligned cluster slots; layout.py guarantees
        start % block_n == 0), or raw uint8 codes when add_offsets.
      start_blocks: (P,) int32 -- slot_start // block_n per pair.
      n_valid: (P,) int32 valid rows per window.
      window: padded window length (rows), multiple of block_n.
      pair_q / pair_lb / bound: early-pruning-v2 bounds (module docstring);
        defaults reproduce the unpruned scan bit-for-bit.

    Returns:
      ((P, k) ascending distances, (P, k) int32 window-row indices,
       (P, 3) int32 [tiles skipped, rows avoided, rows merged]).
    """
    p = tables.shape[0]
    assert window % block_n == 0
    assert codes_t.shape[1] % block_n == 0
    w = codes_t.shape[0]
    if pair_q is None:
        # one virtual query per pair: the running query bound degenerates
        # to the pair's own k-th, i.e. exactly the legacy (uncoupled) scan
        pair_q = jax.lax.iota(jnp.int32, p)
        n_queries = p
        bound = None
    if pair_lb is None:
        pair_lb = jnp.full((p,), NEG_INF, jnp.float32)
    if bound is None:
        bound = jnp.full((n_queries,), jnp.inf, jnp.float32)
    chunks = as_chunks(tables)
    n_tab = chunks.shape[1]
    # clamp the streamed block index so a window that would overrun the last
    # cluster's storage re-reads the final block instead (those rows are
    # already masked by n_valid) -- lets the layout drop its overrun pad
    nblocks = codes_t.shape[1] // block_n
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(p, window // block_n),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(
                (None, n_tab, 128), lambda pi, ti, sb, nv, pq, lb: (pi, 0, 0)
            ),
            pl.BlockSpec(
                (w, block_n),
                lambda pi, ti, sb, nv, pq, lb: (
                    0,
                    jnp.minimum(sb[pi] + ti, nblocks - 1),
                ),
            ),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, k), lambda pi, ti, *_: (pi, 0, 0)),
            pl.BlockSpec((None, 1, k), lambda pi, ti, *_: (pi, 0, 0)),
            pl.BlockSpec((None, 1, 3), lambda pi, ti, *_: (pi, 0, 0)),
        ],
        scratch_shapes=[pltpu.SMEM((n_queries,), jnp.float32)],
    )
    vals, idx, stats = pl.pallas_call(
        functools.partial(
            _adc_topk_windows_kernel, block_n=block_n, path=path,
            add_offsets=add_offsets,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((p, 1, k), tables.dtype),
            jax.ShapeDtypeStruct((p, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((p, 1, 3), jnp.int32),
        ],
        interpret=interpret,
    )(
        start_blocks.astype(jnp.int32),
        n_valid.astype(jnp.int32),
        pair_q.astype(jnp.int32),
        pair_lb.astype(jnp.float32),
        bound.astype(jnp.float32),
        chunks,
        codes_t,
    )
    return vals[:, 0], idx[:, 0], stats[:, 0]


@functools.partial(
    jax.jit, static_argnames=("k", "block_n", "path", "interpret")
)
def adc_topk_pairs_kernel(
    tables: jax.Array,
    addrs: jax.Array,
    n_valid: jax.Array,
    *,
    k: int,
    block_n: int = 1024,
    path: str = "gather",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused scan + top-k where each pair scans its own window.

    Args:
      tables: (P, A) float32 flat tables (one per (query, cluster) pair).
      addrs: (P, L, W) int32 code windows, L % block_n == 0.
      n_valid: (P,) int32 valid rows per window.

    Returns:
      ((P, k) ascending distances, (P, k) int32 window-row indices).
    """
    p = tables.shape[0]
    _, l, w = addrs.shape
    assert l % block_n == 0
    chunks = as_chunks(tables)
    n_tab = chunks.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(p, l // block_n),
        in_specs=[
            pl.BlockSpec((None, n_tab, 128), lambda pi, ti, nv: (pi, 0, 0)),
            pl.BlockSpec((None, w, block_n), lambda pi, ti, nv: (pi, 0, ti)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, k), lambda pi, ti, nv: (pi, 0, 0)),
            pl.BlockSpec((None, 1, k), lambda pi, ti, nv: (pi, 0, 0)),
        ],
    )
    vals, idx = pl.pallas_call(
        functools.partial(
            _adc_topk_pairs_kernel, block_n=block_n, path=path
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((p, 1, k), tables.dtype),
            jax.ShapeDtypeStruct((p, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(n_valid.astype(jnp.int32), chunks, jnp.swapaxes(addrs, 1, 2))
    return vals[:, 0], idx[:, 0]


@functools.partial(
    jax.jit, static_argnames=("k", "block_n", "path", "interpret")
)
def adc_topk_kernel(
    tables: jax.Array,
    addrs: jax.Array,
    n_valid: jax.Array,
    *,
    k: int,
    block_n: int = 1024,
    path: str = "gather",
    interpret: bool = False,
    bound: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused scan + top-k over flat-address codes.

    Args:
      tables: (Q, A) float32 flat tables (one per query/probe).
      addrs: (N, W) int32, N % block_n == 0 (ops.py pads).
      n_valid: (1,) int32 -- true number of rows (padding masked to +inf).
      bound: optional (Q,) f32 per-query initial bound -- a STRICT upper
        bound on the final k-th distance (module docstring).  Tiles whose
        computed minimum exceeds it are never merged; default +inf keeps
        the scan unpruned.

    Returns:
      ((Q, k) ascending distances, (Q, k) int32 row indices).
    """
    q = tables.shape[0]
    n, w = addrs.shape
    assert n % block_n == 0
    if bound is None:
        bound = jnp.full((q,), jnp.inf, jnp.float32)
    chunks = as_chunks(tables)
    n_tab = chunks.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(q, n // block_n),
        in_specs=[
            pl.BlockSpec((None, n_tab, 128), lambda qi, ti, *_: (qi, 0, 0)),
            pl.BlockSpec((w, block_n), lambda qi, ti, *_: (0, ti)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, k), lambda qi, ti, *_: (qi, 0, 0)),
            pl.BlockSpec((None, 1, k), lambda qi, ti, *_: (qi, 0, 0)),
        ],
    )
    vals, idx = pl.pallas_call(
        functools.partial(
            _adc_topk_kernel, block_n=block_n, path=path
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((q, 1, k), tables.dtype),
            jax.ShapeDtypeStruct((q, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(n_valid.astype(jnp.int32), bound.astype(jnp.float32), chunks, addrs.T)
    return vals[:, 0], idx[:, 0]
