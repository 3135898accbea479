"""The online sharded search step (paper Fig. 5 right half) under shard_map.

Per device (== DPU):
  1. build LUTs for the (query, cluster) pairs Algorithm 2 assigned here
     (the host ships q - c residuals, the paper ships the same);
  2. extend each LUT with its cluster's combo partial sums (§4.3);
  3. per-pair fused ADC scan + top-k Pallas kernel (§4.2 + §4.4): either
     the padded-window variant (every pair scans a max-cluster-sized
     window) or the tile-list variant (a flat queue of real code tiles,
     so device work is sum(actual probed rows));
  4. per-query local merge of pair results (thread-heap merge analogue);
  5. one k-sized all-gather over the 'dpu' axis + final top-k
     (replaces the paper's DPU->CPU partial top-k transfer).

Everything is shape-static: P pairs/device, window rows/pair, Q queries.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.lut_build import ext_lut_pairs_kernel

DPU_AXIS = "dpu"


@dataclasses.dataclass
class InFlightSearch:
    """Handle for one dispatched (asynchronous) `sharded_search` step.

    `sharded_search` is dispatched asynchronously by the jax runtime, so the
    output `jax.Array`s held here are futures: creating the handle returns
    as soon as the step is enqueued, and materializing (`collect`) blocks
    until the device finishes.  The handle also carries the host-side plan
    and the per-device load report so the serving layer can overlap planning
    of the next micro-batch with this one's execution and feed observed load
    back into Algorithm 2.

    Attributes:
      out_d: (Q, k) f32 device array of merged distances (in flight).
      out_i: (Q, k) int32 device array of merged global ids (in flight).
      plan: the `SearchPlan` this step executes (untyped to avoid a
        circular import with engine.py).
      dev_rows: (ndev,) int64 rows the device scan visits for this plan —
        the load report consumed by the scheduler's `load_carry`.
      prune_stats: (ndev, 3) int32 device array (in flight): per device,
        [tiles whose body the bound check skipped, valid rows in them,
        rows merged into the running top-k lists] — the scan telemetry
        consumed by `ServingStats`.
      query_bound: (Q,) f32 warm-start bounds this dispatch ran with
        (host copy, so telemetry never recomputes them).
    """

    out_d: jax.Array
    out_i: jax.Array
    plan: object
    dev_rows: np.ndarray
    prune_stats: jax.Array | None = None
    query_bound: np.ndarray | None = None

    def is_ready(self) -> bool:
        """True when the dispatched step has finished on-device.

        Non-blocking (`jax.Array.is_ready`), so the serving layer's
        collect timeout can poll for completion and turn a hung device
        into a fault event instead of blocking forever in `collect`.
        """
        return bool(self.out_d.is_ready() and self.out_i.is_ready())


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def search_static_key(
    *,
    ndev: int,
    n_queries: int,
    pairs_per_dev: int,
    k: int,
    block_n: int,
    window: int,
    path: str,
    add_offsets: bool,
    scan: str = "windows",
    tiles_per_dev: int = 0,
    query_width: int = 0,
) -> tuple:
    """Compilation-cache key of one `sharded_search` instance.

    Two calls whose keys match hit the same jitted executable; the serving
    layer tracks warmed keys with this to guarantee steady-state batches
    never recompile.  `tiles_per_dev` is the tile-list capacity (0 on the
    windows path, where the dummy tile arrays have a fixed width of 1);
    `query_width` is the width S of the plan's query->pair index.
    """
    return (ndev, n_queries, pairs_per_dev, k, block_n, window, path,
            add_offsets, scan, tiles_per_dev, query_width)


def _device_search(
    codes_t,      # (W, cap) codes, column-major  [device-local]
    vec_ids,      # (cap,) int32          [device-local]
    slot_start,   # (S,) int32            [device-local]
    slot_size,    # (S,) int32            [device-local]
    combo_addrs,  # (S, m, L) int32       [device-local]  (m may be 0)
    codebook,     # (M, 256, dsub) f32    [replicated]
    qmc,          # (P, D) f32            [device-local pairs]
    pair_q,       # (P,) int32
    pair_slot,    # (P,) int32
    pair_valid,   # (P,) bool
    query_pairs,  # (Q, S) int32 each query's pair slots, P = none
    tile_pair,    # (T,) int32            [device-local; (1,) dummy on windows]
    tile_block,   # (T,) int32
    tile_row0,    # (T,) int32
    pair_lb,      # (P,) f32 per-pair ADC distance lower bounds
    query_bound,  # (Q,) f32 warm-start bounds      [replicated]
    *,
    n_queries: int,
    k: int,
    block_n: int,
    window: int,
    path: str,
    add_offsets: bool,
    scan: str,
    interpret: bool | None,
):
    p, d_dim = qmc.shape
    m = codebook.shape[0]
    dsub = codebook.shape[2]

    # --- stage (b): LUT construction on device ------------------------------
    # (named scopes label the stages' XLA ops in a device profile's op
    # metadata; they change no instruction)
    with jax.named_scope("lut"):
        luts = ops.build_luts(codebook, qmc.reshape(p, m, dsub))  # (P, M, 256)
        if combo_addrs.shape[1] > 0:
            pair_combos = combo_addrs[pair_slot]  # (P, m_combos, L)
            t_pad = (
                -(-(m * 256 + combo_addrs.shape[1] + 1) // ops.LANE) * ops.LANE
            )
            tables = ext_lut_pairs_kernel(
                luts, pair_combos, t_pad=t_pad,
                interpret=ops.interpret_mode(interpret),
            )  # (P, A)
        else:
            # plain codes never address past the LUT: the kernels read any
            # address beyond the table as 0, so no sentinel slot is appended
            tables = luts.reshape(p, -1)

    # --- stages (c)+(d): per-pair fused scan + top-k ------------------------
    # both variants stream blocks of the shared code array via scalar
    # prefetch (the HBM->VMEM loop of the DPU); "windows" pads every pair to
    # the max-cluster window, "tiles" walks a flat queue of real tiles only.
    starts = slot_start[pair_slot]  # (P,) block-aligned by layout.py
    n_valid = jnp.where(pair_valid, slot_size[pair_slot], 0)
    if scan == "tiles":
        tv, ti, prune = ops.adc_topk_tiles(
            tables, codes_t, tile_pair, tile_block, tile_row0, n_valid, k,
            block_n=block_n, path=path, add_offsets=add_offsets,
            interpret=interpret, pair_q=pair_q, pair_lb=pair_lb,
            bound=query_bound, n_queries=n_queries, with_stats=True,
        )  # pairs without tiles come back (inf, -1) with zero stats
    else:
        tv, ti, prune = ops.adc_topk_windows(
            tables, codes_t, starts, n_valid, k,
            window=window, block_n=block_n, path=path,
            add_offsets=add_offsets, interpret=interpret,
            pair_q=pair_q, pair_lb=pair_lb,
            bound=query_bound, n_queries=n_queries, with_stats=True,
        )  # (P, k) dists, (P, k) window-row idx, (P, 3) scan counters
    prune_dev = prune.sum(axis=0).reshape(1, 3)  # (1, 3) device totals

    tv = jnp.where(pair_valid[:, None], tv, jnp.inf)
    # device row of each candidate; slots past a pair's candidates (+inf)
    # carry -1
    rows = jnp.where(
        (ti >= 0) & jnp.isfinite(tv), starts[:, None] + ti, -1
    )                                               # (P, k)

    # --- per-query local merge (thread-local heap merge analogue) -----------
    # gather each query's pair lists (row P: the (+inf, -1) dummy) in
    # ascending slot order, so top_k's lowest-index tie-break is the
    # (pair slot, rank) order of a merge over every pair of the device
    with jax.named_scope("local_merge"):
        tv = jnp.concatenate([tv, jnp.full((1, k), jnp.inf, tv.dtype)])
        rows = jnp.concatenate([rows, jnp.full((1, k), -1, rows.dtype)])
        cand_d = tv[query_pairs].reshape(n_queries, -1)        # (Q, S*k)
        cand_r = rows[query_pairs].reshape(n_queries, -1)
        neg, sel = jax.lax.top_k(-cand_d, k)                   # (Q, k)
        local_d = -neg
        local_r = jnp.take_along_axis(cand_r, sel, axis=-1)
        # global ids for the Q * k survivors only (a gather over every
        # pair's rows compiles for tens of seconds on TPU)
        local_i = jnp.where(
            local_r >= 0, vec_ids[jnp.clip(local_r, 0, None)], -1
        )

    # --- global merge over the 'dpu' axis ------------------------------------
    with jax.named_scope("global_merge"):
        all_d = jax.lax.all_gather(local_d, DPU_AXIS, axis=0)  # (ndev, Q, k)
        all_i = jax.lax.all_gather(local_i, DPU_AXIS, axis=0)
        ndev = all_d.shape[0]
        all_d = jnp.moveaxis(all_d, 0, 1).reshape(n_queries, ndev * k)
        all_i = jnp.moveaxis(all_i, 0, 1).reshape(n_queries, ndev * k)
        neg, sel = jax.lax.top_k(-all_d, k)
        out_d = -neg
        out_i = jnp.take_along_axis(all_i, sel, axis=-1)
    return out_d, out_i, prune_dev


def rerank_static_key(
    *,
    ndev: int,
    n_queries: int,
    k_cand: int,
    k_out: int,
    dim: int,
    row_capacity: int,
    ids_capacity: int,
    dtype: str,
    block_k: int = 0,
) -> tuple:
    """Compilation-cache key of one `sharded_rerank` instance.

    Mirrors `search_static_key`: the serving layer warms one executable per
    key and asserts steady-state batches never recompile.  `row_capacity` /
    `ids_capacity` come from `RawStore.shape_key()` -- pow2-bucketed, so
    moderate churn keeps the key stable.  `block_k` is the tuned re-rank
    candidate-block width (0 = the kernel default)."""
    return ("rerank", ndev, n_queries, k_cand, k_out, dim,
            row_capacity, ids_capacity, dtype, block_k)


def _device_rerank(
    raw,        # (rcap, D) f32/bf16     [device-local]
    id_dev,     # (ids_cap,) int32       [replicated]
    id_row,     # (ids_cap,) int32       [replicated]
    queries,    # (Q, D) f32             [replicated]
    cand,       # (Q, Kc) int32 global candidate ids  [replicated]
    *,
    k_out: int,
    block_k: int,
    interpret: bool | None,
):
    my = jax.lax.axis_index(DPU_AXIS)
    n_ids = id_dev.shape[0]
    cid = jnp.clip(cand, 0, n_ids - 1)
    owner = id_dev[cid]                                  # (Q, Kc)
    valid = (cand >= 0) & (owner >= 0)
    owned = valid & (owner == my)
    rows = jnp.where(owned, id_row[cid], 0)
    with jax.named_scope("rerank_gather"):
        vecs = raw[rows]                                 # (Q, Kc, D) gather
    part = ops.rerank_dists(
        queries, vecs, block_k=block_k, interpret=interpret
    )
    part = jnp.where(owned, part, 0.0)
    # each (q, c) has exactly ONE owning device, so this f32 psum adds the
    # true partial to zeros only -- bit-exact in any reduction order
    dists = jax.lax.psum(part, DPU_AXIS)
    dists = jnp.where(valid, dists, jnp.inf)

    # tie-aware selection: stable sort by exact distance, ties broken by
    # ADC candidate position (so the cascade's output is deterministic and
    # matches the brute-force oracle's stable argsort bit-for-bit)
    with jax.named_scope("rerank_select"):
        sel = jnp.argsort(dists, axis=-1, stable=True)[:, :k_out]
    out_d = jnp.take_along_axis(dists, sel, axis=-1)
    out_i = jnp.take_along_axis(cand, sel, axis=-1)
    out_i = jnp.where(jnp.isfinite(out_d), out_i, -1)
    return out_d, out_i


@functools.partial(
    jax.jit, static_argnames=("mesh", "k_out", "block_k", "interpret")
)
def sharded_rerank(
    raw, id_dev, id_row, queries, cand,
    *,
    mesh: jax.sharding.Mesh,
    k_out: int,
    block_k: int = 0,
    interpret: bool | None = None,
):
    """Exact re-rank of ADC candidates against the sharded raw-vector store.

    Second cascade stage: `cand` ((Q, Kc) int32) holds the global ids the
    overfetched ADC scan surfaced (−1 = absent).  Each device gathers the
    candidates whose home it is from its `raw` shard ((ndev, rcap, D)),
    computes exact f32 squared-L2 partials with the Pallas re-rank kernel,
    and a psum over the 'dpu' axis reassembles full distances (bit-exact:
    one non-zero contributor per element).  Selection is a stable argsort,
    ties broken by candidate position, so the output top-`k_out` is
    bit-identical to a brute-force fp32 re-rank of the same candidate set.

    Candidates that are −1 or unmapped in `id_dev` come back as
    (+inf, −1) and sort last.  `block_k` is the tuned candidate-block
    width handed to the re-rank kernel (0 = default; bit-identical at
    every value).  Returns (out_d (Q, k_out), out_i (Q, k_out)), both
    replicated.
    """
    spec_dev = jax.sharding.PartitionSpec(DPU_AXIS)
    spec_rep = jax.sharding.PartitionSpec()
    fn = functools.partial(
        _device_rerank, k_out=k_out, block_k=block_k, interpret=interpret
    )

    def per_device(raw, id_dev, id_row, queries, cand):
        return fn(raw[0], id_dev, id_row, queries, cand)

    return _shard_map(
        per_device,
        mesh=mesh,
        in_specs=(spec_dev, spec_rep, spec_rep, spec_rep, spec_rep),
        out_specs=(spec_rep, spec_rep),
    )(raw, id_dev, id_row, queries, cand)


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "n_queries", "k", "block_n", "window", "path",
        "add_offsets", "scan", "interpret",
    ),
)
def sharded_search(
    codes_t, vec_ids, slot_start, slot_size, combo_addrs,
    codebook, qmc, pair_q, pair_slot, pair_valid, query_pairs,
    tile_pair, tile_block, tile_row0,
    pair_lb, query_bound,
    *,
    mesh: jax.sharding.Mesh,
    n_queries: int,
    k: int,
    block_n: int,
    window: int,
    path: str = "gather",
    add_offsets: bool = False,
    scan: str = "windows",
    interpret: bool | None = None,
):
    """shard_map wrapper: leading dim of device arrays is the 'dpu' axis.

    `codes_t` is the column-major code array, (ndev, W, cap);
    `query_pairs` ((ndev, Q, S) int32, `core.scheduling.query_pair_index`)
    lists each query's pair slots per device for the per-query merge.

    `scan` selects the device scan variant: "windows" (padded per-pair
    windows) or "tiles" (flat work queue; `tile_*` are (ndev, T) arrays
    from `emit_tiles`).  On the windows path `tile_*` are unused (pass any
    (ndev, 1) int32 arrays; a fixed width keeps the jit cache stable).

    `pair_lb` ((ndev, P) f32) and `query_bound` ((Q,) f32, replicated)
    drive the early-pruning whole-tile skip; (-inf, +inf) sentinels run
    the scan unpruned with the same executable.  Returns
    (out_d (Q, k), out_i (Q, k), prune_stats (ndev, 3) int32:
    [tiles skipped, rows avoided, rows merged] per device).
    """
    spec_dev = jax.sharding.PartitionSpec(DPU_AXIS)
    spec_rep = jax.sharding.PartitionSpec()
    fn = functools.partial(
        _device_search,
        n_queries=n_queries, k=k, block_n=block_n,
        window=window, path=path, add_offsets=add_offsets,
        scan=scan, interpret=interpret,
    )

    def per_device(*args):
        # strip the leading (size-1) shard dim of the sharded arguments
        return fn(*(a if s is spec_rep else a[0] for a, s in zip(args, specs)))

    specs = (
        spec_dev, spec_dev, spec_dev, spec_dev, spec_dev,
        spec_rep, spec_dev, spec_dev, spec_dev, spec_dev, spec_dev,
        spec_dev, spec_dev, spec_dev, spec_dev, spec_rep,
    )
    return _shard_map(
        per_device,
        mesh=mesh,
        in_specs=specs,
        out_specs=(spec_rep, spec_rep, spec_dev),
    )(
        codes_t, vec_ids, slot_start, slot_size, combo_addrs,
        codebook, qmc, pair_q, pair_slot, pair_valid, query_pairs,
        tile_pair, tile_block, tile_row0,
        pair_lb, query_bound,
    )
