"""MemANNSEngine: the end-to-end system of paper Fig. 5 behind one object.

Offline (build): IVF+PQ index -> frequency estimation from a historical query
log -> Algorithm-1 placement (with replication + co-location) -> optional
§4.3 co-occurrence re-encoding -> per-device packed shards.

Online (search): host-side cluster filtering + Algorithm-2 scheduling, then
one jitted shard_map step (LUT build, fused ADC+top-k, hierarchical merge).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import IVFPQIndex, build_index, filter_clusters
from repro.kernels.adc_topk import tile_queue_chunks
from repro.obs.trace import NULL_TRACER
from repro.core.placement import (
    Placement,
    estimate_frequencies,
    place_clusters,
)
from repro.core.scheduling import (
    ArraySchedule,
    count_tiles,
    densify_schedule,
    emit_tiles,
    query_pair_index,
    residual_bounds,
    schedule_queries,
    subspace_code_norms,
    warm_start_bounds,
)
from repro.retrieval.layout import (
    DeviceShards,
    RawStore,
    build_raw_store,
    build_shards,
    default_slack,
)
from repro.retrieval.search import (
    DPU_AXIS,
    InFlightSearch,
    sharded_rerank,
    sharded_search,
)


def make_dpu_mesh(devices=None) -> jax.sharding.Mesh:
    """Flat 1-D mesh over all devices: device == the paper's DPU."""
    if devices is None:
        devices = jax.devices()
    return jax.sharding.Mesh(np.asarray(devices), (DPU_AXIS,))


def round_capacity(max_pairs: int, floor: int = 8) -> int:
    """Round a pair count up to the next power-of-two capacity bucket.

    Serving reuses these buckets so `sharded_search` compiles once per
    bucket instead of once per batch shape.
    """
    return max(floor, 1 << math.ceil(math.log2(max(max_pairs, 1))))


@dataclasses.dataclass
class SearchPlan:
    """Densified host-side plan for one `sharded_search` invocation.

    Produced by `MemANNSEngine.plan_batch` (cluster filtering + Algorithm 2
    + array densify); consumed by `MemANNSEngine.execute_plan`.
    """

    qmc_pairs: np.ndarray   # (ndev, P, D) f32 per-pair query - centroid
    pair_q: np.ndarray      # (ndev, P) int32 query index
    pair_slot: np.ndarray   # (ndev, P) int32 local cluster slot
    pair_valid: np.ndarray  # (ndev, P) bool
    schedule: ArraySchedule | None  # None for synthetic warmup plans
    n_queries: int
    pairs_per_dev: int
    # (ndev, Q, S) each query's pair slots per device, padded with P
    # (`query_pair_index`); S is the plan's query width
    query_pairs: np.ndarray
    # tile-list work queue (scan="tiles" only; None on the windows path)
    tile_pair: np.ndarray | None = None   # (ndev, T) int32, P marks dummies
    tile_block: np.ndarray | None = None  # (ndev, T) int32 code-block index
    tile_row0: np.ndarray | None = None   # (ndev, T) int32 window-rel row
    tiles_per_dev: int = 0
    # early-pruning bound arrays (None = plan executes unpruned; the
    # executable is identical either way -- bounds are runtime data)
    pair_lb: np.ndarray | None = None      # (ndev, P) f32 pair lower bounds
    probed_ub: np.ndarray | None = None    # (Q, nprobe) f32 cluster upper bds
    probed_sizes: np.ndarray | None = None  # (Q, nprobe) int64 cluster sizes
    # failover coverage accounting (planned under a live-device mask only):
    # probed (query, cluster) pairs whose every replica is on a dead device.
    # None = planned with all devices live.
    lost_q: np.ndarray | None = None       # (L,) int32 query index
    lost_c: np.ndarray | None = None       # (L,) int32 cluster id

    @property
    def scan(self) -> str:
        """Device scan variant this plan was built for."""
        return "tiles" if self.tile_pair is not None else "windows"

    @property
    def pruned(self) -> bool:
        """True when this plan carries early-pruning bounds."""
        return self.pair_lb is not None

    def degraded_mask(self) -> np.ndarray:
        """(Q,) bool: queries with at least one unreachable probed cluster.

        Such queries still return their best-effort top-k over every
        reachable cluster; the serving layer surfaces the flag (plus the
        exact lost pairs) instead of crashing or silently under-reporting.
        """
        mask = np.zeros(self.n_queries, bool)
        if self.lost_q is not None and self.lost_q.size:
            mask[self.lost_q] = True
        return mask

    def query_bounds(self, k: int) -> np.ndarray:
        """(Q,) strict warm-start upper bounds on the k-th output distance.

        Computed per dispatch (the plan itself is k-agnostic) from the
        probed clusters' distance upper bounds and sizes; +inf everywhere
        when the plan is unpruned or has no probe metadata (warmup plans).
        """
        if self.probed_ub is None or self.probed_sizes is None:
            return np.full(self.n_queries, np.inf, np.float32)
        return warm_start_bounds(self.probed_ub, self.probed_sizes, k)


@dataclasses.dataclass
class MemANNSEngine:
    """End-to-end engine state + the host half of the online path.

    Knobs (all also reachable through `build(...)`):
      path: ADC scan addressing variant — "gather" (per-row LUT gathers) or
        "flat" (direct-address extended LUTs; required by co-occ shards).
      scan: device scan variant — "tiles" (flat queue of real code tiles,
        work ∝ probed rows) or "windows" (every pair padded to the max
        cluster window).  Bit-identical outputs; see docs/ARCHITECTURE.md.
      prune: early-pruning v2 — sound per-pair lower bounds + warm-start
        query bounds let the kernel skip whole tiles exactly.  `False`
        plans the unpruned reference scan (same executable, ±inf bounds).
      rerank: "off" returns ADC (quantized) distances; "exact" runs the
        two-stage cascade — the ADC scan overfetches `k_prime(k)`
        candidates, then the Pallas re-rank kernel recomputes exact f32
        distances against the raw-vector shard and the final top-k is
        re-selected (requires `raw`; see `dispatch_rerank`).
      k_overfetch: candidate count k' fed to the re-rank stage; 0 = auto
        (4·k).  Rounded up to a pow2 bucket (floor k) either way, so
        serving warms one executable per (k, bucket) pair.
      rerank_block: re-rank kernel candidate-block width per grid step
        (0 = the kernel default, LANE).  Tuned geometry knob — results are
        bit-identical at every value (see kernels.rerank).
      tile_floor: minimum tiles-per-device capacity for auto-sized tile
        queues (0 = pairs_per_dev).  A larger floor trades padding
        (dummy tiles) for fewer distinct warmed tile buckets; clamped to
        the reachable `tile_buckets` ladder so warmup coverage holds.
      interpret: force Pallas interpret mode (None = auto: interpret
        everywhere except real TPU backends).

    The tuned-geometry surface (`block_n` via `retile`, `rerank_block`,
    `tile_floor`) is applied as a unit by `apply_geometry`; `geometry()`
    reports the current values.  `core.autotune` sweeps candidates and
    the serving layer applies the winner at warmup.

    `raw` is the per-device raw-vector shard backing the cascade (built by
    `build(store_raw=True)` or attached via `attach_raw_store`); `delta` is
    the DeltaIndex buffer once mutation is enabled.  `_dev_arrays` /
    `_raw_arrays` cache the sharded device copies of the packed arrays —
    invalidated by compaction when shapes or contents change.
    """

    index: IVFPQIndex
    placement: Placement
    shards: DeviceShards
    mesh: jax.sharding.Mesh
    path: str = "gather"
    scan: str = "tiles"  # device scan variant: "tiles" | "windows"
    prune: bool = True   # early-pruning v2 bounds (exact; False = reference)
    rerank: str = "off"  # exact re-rank cascade: "off" | "exact"
    k_overfetch: int = 0  # cascade candidate count k' (0 = auto: 4k)
    rerank_block: int = 0  # re-rank candidate-block width (0 = kernel default)
    tile_floor: int = 0   # min tiles_per_dev capacity (0 = pairs_per_dev)
    interpret: bool | None = None
    freqs: np.ndarray | None = None   # f_i estimate (kept for re-placement)
    delta: "object | None" = None     # DeltaIndex once mutation is enabled
    raw: RawStore | None = None       # raw-vector shard (rerank="exact")
    # span tracer for engine-level sub-phases (schedule/densify/emit_tiles,
    # rerank_dispatch, compaction internals).  Engine spans are child-only
    # (root=False): they record when nested under a sampled serving batch
    # span and evaporate otherwise, so a shared engine never pollutes
    # another ServingEngine's trace ring.  ServingEngine(tracer=...)
    # installs its tracer here.
    tracer: "object" = NULL_TRACER
    _dev_arrays: tuple | None = None
    _raw_arrays: tuple | None = None
    _code_norms: np.ndarray | None = None  # (M,) cached codebook max norms

    @classmethod
    def build(
        cls,
        key: jax.Array,
        xs: np.ndarray,
        n_clusters: int,
        m: int,
        mesh: jax.sharding.Mesh | None = None,
        history_queries: np.ndarray | None = None,
        nprobe_history: int = 32,
        use_cooc: bool = False,
        n_combos: int = 256,
        block_n: int = 1024,
        min_length_reduction: float = 0.0,
        kmeans_iters: int = 15,
        pq_iters: int = 10,
        train_subsample: int | None = None,
        path: str = "gather",
        scan: str = "tiles",
        prune: bool = True,
        rerank: str = "off",
        k_overfetch: int = 0,
        rerank_block: int = 0,
        tile_floor: int = 0,
        store_raw: bool | None = None,
        raw_dtype: str = "float32",
        opq_iters: int = 0,
        interpret: bool | None = None,
        mutable: bool = False,
        delta_capacity: int = 4096,
        cap_slack: float | None = None,
        slot_slack: int | None = None,
        window_slack: int | None = None,
    ) -> "MemANNSEngine":
        """Offline build.  `mutable=True` enables online inserts/deletes:
        a DeltaIndex buffer (`delta_capacity` rows, pow2-bucketed) is
        allocated up front and the shard packing reserves growth slack
        (`cap_slack`/`slot_slack`/`window_slack`, defaulting to 50% rows /
        4 slots / 2 window blocks) so incremental compactions keep every
        compiled shape stable under moderate churn.

        `rerank="exact"` enables the full-precision re-rank cascade and
        (unless `store_raw=False`) packs the build vectors into a
        per-device raw shard — `raw_dtype` picks its on-device precision
        ("float32" | "bfloat16").  `opq_iters > 0` learns an OPQ-style
        rotation before PQ training (alternating encode / Procrustes
        steps), lifting the ADC candidate quality feeding the cascade;
        centroids and codes then live in the rotated space, queries are
        rotated on entry, and the raw shard (and therefore the exact
        re-rank) stays in the original space — squared L2 is rotation
        invariant, so the cascade contract is unchanged.
        `train_subsample` caps the rows k-means and PQ train on (every row
        is still assigned and encoded; see `core.index.build_index`).

        All knobs compose: `use_cooc=True` with `mutable=True` buffers
        inserts plain-coded in the delta (same jitted assign/encode path)
        and re-mines/re-encodes only the changed clusters at compaction
        (`retrieval.layout.update_shards`), keeping every compiled shape
        stable — the co-occ shard width is reserved at the full plain
        width when mutable.  See tests/test_feature_matrix.py for the
        scan × cooc × mutable × prune × rerank equivalence wall."""
        # unsupported arguments fail before any expensive work (the
        # k-means build + Algorithm-1 placement below can take minutes)
        if rerank not in ("off", "exact"):
            raise ValueError(f"rerank must be 'off' or 'exact', got {rerank!r}")
        mesh = mesh or make_dpu_mesh()
        ndev = math.prod(mesh.devices.shape)
        index = build_index(
            key, xs, n_clusters, m, kmeans_iters=kmeans_iters,
            pq_iters=pq_iters, train_subsample=train_subsample,
            opq_iters=opq_iters,
        )
        # f_i from the historical query log (paper §4.1's predictor)
        if history_queries is not None and len(history_queries):
            probed, _ = filter_clusters(
                jnp.asarray(index.centroids),
                jnp.asarray(history_queries, jnp.float32),
                min(nprobe_history, n_clusters),
            )
            freqs = estimate_frequencies(np.asarray(probed), n_clusters)
        else:
            freqs = np.ones(n_clusters) / n_clusters
        placement = place_clusters(
            index.cluster_sizes().astype(np.float64),
            freqs,
            ndev,
            centroids=index.centroids,
        )
        # layout slack derives from the chosen block_n (layout.default_slack)
        # so a tuned tile height keeps the same row headroom under churn
        d_cap, d_slot, d_win = default_slack(block_n, mutable)
        shards = build_shards(
            index,
            placement,
            use_cooc=use_cooc,
            n_combos=n_combos,
            block_n=block_n,
            min_length_reduction=min_length_reduction,
            cap_slack=(d_cap if cap_slack is None else cap_slack) if mutable else 0.0,
            slot_slack=(d_slot if slot_slack is None else slot_slack) if mutable else 0,
            window_slack=(
                (d_win if window_slack is None else window_slack) if mutable else 0
            ),
        )
        if store_raw is None:
            store_raw = rerank == "exact"
        raw = None
        if store_raw:
            raw = build_raw_store(
                index, placement, xs, dtype=raw_dtype,
                cap_slack=0.5 if mutable else 0.0,
            )
        eng = cls(
            index=index,
            placement=placement,
            shards=shards,
            mesh=mesh,
            path=path,
            scan=scan,
            prune=prune,
            rerank=rerank,
            k_overfetch=k_overfetch,
            rerank_block=rerank_block,
            tile_floor=tile_floor,
            interpret=interpret,
            freqs=freqs,
            raw=raw,
        )
        if mutable:
            from repro.retrieval.mutation import ensure_delta

            ensure_delta(eng, delta_capacity)
        return eng

    # ------------------------- online mutation ------------------------- #

    def insert(self, ids: np.ndarray, vectors: np.ndarray) -> int:
        """Buffer new PQ-encoded vectors; visible to the next search."""
        from repro.retrieval.mutation import insert_into

        return insert_into(self, ids, vectors)

    def delete(self, ids: np.ndarray) -> int:
        """Tombstone ids; filtered from the next search onward."""
        from repro.retrieval.mutation import delete_from

        return delete_from(self, ids)

    def compact(self, replace_threshold: float = 0.25):
        """Merge delta + drop tombstones; incremental re-place + repack.

        Returns a `repro.retrieval.mutation.CompactionReport`."""
        from repro.retrieval.mutation import compact_engine

        return compact_engine(self, replace_threshold=replace_threshold)

    @property
    def mutation_active(self) -> bool:
        """True when searches must consult the delta layer."""
        return self.delta is not None and self.delta.active

    # ------------------------------------------------------------------ #

    def _sharding_specs(self):
        spec_dev = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(DPU_AXIS)
        )
        spec_rep = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec()
        )
        return spec_dev, spec_rep

    def _device_put(self):
        """Shard the packed arrays over the mesh once, cache on device."""
        if self._dev_arrays is not None:
            return self._dev_arrays
        spec_dev, spec_rep = self._sharding_specs()
        s = self.shards
        # one batched transfer for the whole pytree (5 sharded + 1 replicated);
        # codes ship column-major (ndev, W, cap), the layout the scan kernels
        # stream lane-dense
        self._dev_arrays = jax.device_put(
            (
                np.ascontiguousarray(s.codes.transpose(0, 2, 1)),
                s.vec_ids,
                s.slot_start,
                s.slot_size,
                s.combo_addrs,
                self.index.codebook.astype(np.float32),
            ),
            (spec_dev,) * 5 + (spec_rep,),
        )
        return self._dev_arrays

    def k_prime(self, k: int) -> int:
        """Cascade candidate count k' for a final top-`k` (pow2-bucketed).

        `k_overfetch` when set (clamped to >= k), else 4·k; rounded up to a
        power-of-two bucket with floor k so the serving layer warms exactly
        one re-rank executable per (k, bucket)."""
        want = self.k_overfetch if self.k_overfetch > 0 else 4 * k
        return round_capacity(max(want, k), floor=max(k, 1))

    # ---------------------- tuned kernel geometry ---------------------- #

    def geometry(self):
        """Current `core.autotune.KernelGeometry` of this engine."""
        from repro.core.autotune import KernelGeometry

        return KernelGeometry(
            block_n=self.shards.block_n,
            rerank_block=self.rerank_block,
            tile_floor=self.tile_floor,
        )

    def apply_geometry(self, geo) -> bool:
        """Apply a tuned `KernelGeometry` (autotuner output) as a unit.

        Sets `rerank_block`/`tile_floor` and, when the tile height
        differs from the built shards, retiles the packed layout (see
        `retile`).  `block_n=0` means "keep the build-time tile height"
        (the honest in-repo default for unmeasured backends).  Results
        are bit-identical before/after by construction — geometry is
        data layout, never selection order.  Returns True when the
        shards were retiled (callers holding device copies or warm sets
        should treat that as a cold start).
        """
        self.rerank_block = int(getattr(geo, "rerank_block", 0) or 0)
        self.tile_floor = int(getattr(geo, "tile_floor", 0) or 0)
        block_n = int(getattr(geo, "block_n", 0) or 0)
        if block_n and block_n != self.shards.block_n:
            self.retile(block_n)
            return True
        return False

    def retile(self, block_n: int) -> None:
        """Repack the device shards at a new tile height `block_n`.

        The shards are a deterministic function of (index, placement,
        build knobs): cluster slots re-align to the new block_n and the
        co-occ re-mining (when enabled) is seeded by cluster id, so the
        rebuilt encodings are identical and search results are
        bit-identical across tile heights — the per-tile merge's tie
        order is independent of where tile boundaries fall (see
        kernels.adc_topk) and the pruning skips are results-preserving.
        Mutable layout slack is re-derived for the new block_n
        (`layout.default_slack`); the delta buffer and raw store are
        untouched; the cached device copy of the packed arrays is
        dropped (shapes changed).
        """
        s = self.shards
        cap_s, slot_s, win_s = default_slack(block_n, self.delta is not None)
        self.shards = build_shards(
            self.index,
            self.placement,
            use_cooc=s.n_combos > 0,
            n_combos=s.n_combos if s.n_combos > 0 else 256,
            combo_len=s.combo_addrs.shape[3] if s.n_combos > 0 else 3,
            block_n=block_n,
            min_length_reduction=s.min_length_reduction,
            mine_rows=s.mine_rows,
            compact_dtype=s.codes.dtype != np.int32,
            cap_slack=cap_s,
            slot_slack=slot_s,
            window_slack=win_s,
        )
        self._dev_arrays = None

    def attach_raw_store(
        self,
        xs: np.ndarray,
        xs_ids: np.ndarray | None = None,
        dtype: str = "float32",
    ):
        """Build + attach the raw-vector shard for an existing engine.

        `xs` are ORIGINAL-space vectors; `xs_ids[i]` is the global id of
        row i (defaults to 0..N-1, the fresh-build layout where
        `index.vec_ids` are positions into the build input).  Every id in
        `index.vec_ids` must be covered."""
        self.raw = build_raw_store(
            self.index, self.placement, xs, xs_ids=xs_ids, dtype=dtype,
            cap_slack=0.5 if self.delta is not None else 0.0,
        )
        self._raw_arrays = None
        return self.raw

    def schedule_batch(
        self,
        queries: np.ndarray,
        nprobe: int,
        load_carry: np.ndarray | None = None,
        live: np.ndarray | None = None,
    ) -> tuple[ArraySchedule, np.ndarray, np.ndarray]:
        """Host side: cluster filtering (stage a) + vectorized Algorithm 2.

        `load_carry` is the optional (ndev,) carried-load bias (see
        `schedule_queries`); the serving layer threads its EWMA of
        per-device scanned rows through here.  `live` is the optional
        live-device mask (replica failover — see `schedule_queries`).

        With an OPQ rotation the queries are rotated here — centroids and
        PQ codes live in the rotated space, so everything downstream of
        this point (residuals, LUTs, ADC scan) is rotated too.  The exact
        re-rank path is NOT: `dispatch_rerank` takes original-space
        queries against the original-space raw shard.
        """
        probed, qmc = filter_clusters(
            jnp.asarray(self.index.centroids),
            jnp.asarray(self.index.rotate(queries), jnp.float32),
            nprobe,
        )
        probed = np.asarray(probed)
        schedule = schedule_queries(
            probed, self.index.cluster_sizes(), self.placement,
            load_carry=load_carry, live=live,
        )
        return schedule, probed, np.asarray(qmc)

    def code_norms(self) -> np.ndarray:
        """(M,) cached per-subspace max codeword norms (bound inputs)."""
        if self._code_norms is None:
            self._code_norms = subspace_code_norms(self.index.codebook)
        return self._code_norms

    def plan_batch(
        self,
        queries: np.ndarray,
        nprobe: int,
        pairs_per_dev: int | None = None,
        capacity_floor: int = 8,
        tiles_per_dev: int | None = None,
        load_carry: np.ndarray | None = None,
        prune: bool | None = None,
        live: np.ndarray | None = None,
        query_width: int | None = None,
    ) -> SearchPlan:
        """Host-side online phase: filter + schedule + array densify.

        Everything after `filter_clusters` is pure numpy array ops — no
        per-pair Python loops survive on this path.  With `scan="tiles"`
        the plan additionally carries the flat tile work queue; its
        capacity is rounded to `pairs_per_dev * 2^i` buckets so serving
        can pre-warm every reachable executable.  `load_carry` biases the
        schedule toward cold devices (see `schedule_queries`).

        With pruning (default `self.prune`) the plan also carries sound
        per-pair ADC distance lower bounds (scattered alongside the
        residuals) plus each query's probed-cluster upper bounds/sizes
        (for the per-dispatch warm-start bound), and the tile queue is
        ordered best-first (ascending lower bound) so the kernel's running
        k-th tightens within the first few tiles.  `prune=False` plans the
        exact pre-bounds reference scan.

        `live` plans around dead devices (replica failover): their pairs
        re-route to surviving replicas and unreachable (query, cluster)
        pairs land in the plan's `lost_q`/`lost_c` coverage accounting.
        Unreachable clusters are also zeroed out of the warm-start size
        accounting — a bound may only count rows the scan will actually
        visit, otherwise degraded queries could prune reportable rows.

        `query_width` (default `nprobe`) is the per-device width of the
        plan's query->pair index; it is part of the executable's shape, so
        a caller planning at several nprobe values passes the largest to
        keep one executable per pair bucket.
        """
        queries = np.asarray(queries, np.float32)
        q_n = queries.shape[0]
        ndev = self.shards.ndev
        prune = self.prune if prune is None else prune
        tr = self.tracer
        with tr.span("schedule", root=False):
            schedule, probed, qmc = self.schedule_batch(
                queries, nprobe, load_carry=load_carry, live=live
            )

        max_pairs = int(schedule.counts_per_dev().max(initial=0))
        if pairs_per_dev is None:
            # round up to limit jit re-compiles across batches
            pairs_per_dev = round_capacity(max_pairs, floor=capacity_floor)

        # densify the index arrays (raises on capacity overflow), then
        # scatter the per-pair residuals with the same packing coordinates
        with tr.span("densify", root=False):
            pair_q, pair_slot, pair_valid = densify_schedule(
                schedule, self.shards.local_slot, pairs_per_dev
            )
            query_pairs = query_pair_index(
                pair_q, pair_valid, q_n, query_width or nprobe
            )
            order, d_sorted, pos = schedule.device_positions()
            pq, pc = schedule.pair_q[order], schedule.pair_c[order]
            # column of each pair's cluster within its probed row (qmc lookup)
            cols = np.argmax(probed[pq] == pc[:, None], axis=1)
            qmc_pairs = np.zeros(
                (ndev, pairs_per_dev, queries.shape[1]), np.float32
            )
            qmc_pairs[d_sorted, pos] = qmc[pq, cols]

            pair_lb = probed_ub = probed_sizes = None
            if prune:
                lb, ub = residual_bounds(qmc, self.code_norms())
                # densify-padding pairs get +inf: their (empty) tile bodies
                # are skipped for free and their (inf, -1) outputs unchanged
                pair_lb = np.full((ndev, pairs_per_dev), np.inf, np.float32)
                pair_lb[d_sorted, pos] = lb[pq, cols]
                probed_ub = ub
                probed_sizes = self.index.cluster_sizes()[probed]
                if schedule.lost_c is not None and schedule.lost_c.size:
                    # unreachable clusters contribute no scannable rows:
                    # the warm-start bound must not count them (soundness
                    # of degraded queries' best-effort top-k)
                    unreach = np.zeros(
                        self.index.cluster_sizes().shape[0], bool
                    )
                    unreach[schedule.lost_c] = True
                    probed_sizes = np.where(unreach[probed], 0, probed_sizes)

        tile_pair = tile_block = tile_row0 = None
        tiles_cap = 0
        if self.scan == "tiles":
            s = self.shards
            if tiles_per_dev is None:
                nv = np.take_along_axis(s.slot_size, pair_slot, axis=1)
                max_tiles = int(
                    count_tiles(pair_valid, nv, s.block_n).max(initial=0)
                )
                floor = pairs_per_dev
                if self.tile_floor > 0:
                    # tuned floor, clamped to the reachable tile-bucket
                    # ladder (pairs_per_dev * 2^i up to pow2(window/block))
                    # so serving warmup still covers every capacity
                    wb2 = 1 << math.ceil(
                        math.log2(max(s.window // s.block_n, 1))
                    )
                    floor = min(
                        round_capacity(self.tile_floor, floor=pairs_per_dev),
                        pairs_per_dev * wb2,
                    )
                tiles_per_dev = round_capacity(max_tiles, floor=floor)
            tiles_cap = tiles_per_dev
            with tr.span("emit_tiles", root=False):
                tile_pair, tile_block, tile_row0 = emit_tiles(
                    pair_slot, pair_valid, s.slot_start, s.slot_size,
                    s.block_n, tiles_per_dev,
                    pair_key=pair_lb if prune else None,
                )
        return SearchPlan(
            qmc_pairs=qmc_pairs,
            pair_q=pair_q,
            pair_slot=pair_slot,
            pair_valid=pair_valid,
            schedule=schedule,
            n_queries=q_n,
            pairs_per_dev=pairs_per_dev,
            query_pairs=query_pairs,
            tile_pair=tile_pair,
            tile_block=tile_block,
            tile_row0=tile_row0,
            tiles_per_dev=tiles_cap,
            pair_lb=pair_lb,
            probed_ub=probed_ub,
            probed_sizes=probed_sizes,
            lost_q=schedule.lost_q,
            lost_c=schedule.lost_c,
        )

    def plan_dev_rows(self, plan: SearchPlan) -> np.ndarray:
        """(ndev,) code rows the device scan visits per device for `plan`.

        This is the per-batch load report the serving layer folds into its
        EWMA `load_carry`: on the tiles path it is the real (non-dummy)
        tile count times the tile height; on the windows path it is the
        valid rows of each scheduled pair (the window padding is constant
        per pair and carries no balance signal).
        """
        if plan.scan == "tiles":
            real = (plan.tile_pair != plan.pairs_per_dev).sum(axis=1)
            return real.astype(np.int64) * self.shards.block_n
        nv = np.where(
            plan.pair_valid,
            np.take_along_axis(self.shards.slot_size, plan.pair_slot, axis=1),
            0,
        )
        return nv.sum(axis=1).astype(np.int64)

    def plan_tile_count(self, plan: SearchPlan) -> int:
        """Total non-empty code tiles `plan` dispatches (all devices).

        The denominator of the prune-effectiveness telemetry: on the tiles
        path it is the real (non-dummy) tile count; on the windows path,
        the number of window tiles holding at least one valid row (padding
        tiles past a cluster's end never count — the kernels skip-account
        with the same rule).
        """
        if plan.scan == "tiles":
            return int((plan.tile_pair != plan.pairs_per_dev).sum())
        nv = np.where(
            plan.pair_valid,
            np.take_along_axis(self.shards.slot_size, plan.pair_slot, axis=1),
            0,
        )
        bn = self.shards.block_n
        return int(((nv + bn - 1) // bn).sum())

    def plan_scan_steps(
        self, plan: SearchPlan, q_real: int
    ) -> tuple[int, int, int]:
        """(query, pad, bucket): the scan kernel's grid steps for `plan`,
        summed over devices, by what each step serves.

        `query` steps serve one of the first `q_real` queries of the plan
        (the real rows of a padded micro-batch), `pad` steps serve its
        padding rows, and `bucket` steps fill capacity: on the tiles path
        the dummy tiles of the tile bucket plus the kernel's padding of
        the queue to whole chunks, on the windows path the invalid pairs.
        The three add up to the kernel's grid steps.  Host arrays only.
        """
        p = plan.pairs_per_dev
        if plan.scan == "tiles":
            tp = plan.tile_pair
            real = tp != p
            tile_q = np.take_along_axis(
                plan.pair_q, np.minimum(tp, p - 1), axis=1
            )
            query = int((real & (tile_q < q_real)).sum())
            pad = int(real.sum()) - query
            chunk, n_chunks = tile_queue_chunks(tp.shape[1])
            total = tp.shape[0] * chunk * n_chunks
        else:
            per_pair = self.shards.window // self.shards.block_n
            valid = plan.pair_valid
            query = int((valid & (plan.pair_q < q_real)).sum()) * per_pair
            pad = int(valid.sum()) * per_pair - query
            total = valid.size * per_pair
        return query, pad, total - query - pad

    def dispatch_plan(self, plan: SearchPlan, k: int) -> InFlightSearch:
        """Enqueue one shard_map step without blocking on its results.

        The per-batch inputs are shipped as ONE batched `device_put` on a
        pytree with a single sharding spec (one transfer instead of seven),
        and the jitted step is dispatched asynchronously — the returned
        handle holds in-flight `jax.Array`s plus the plan's load report.
        `collect` (or `np.asarray` on the outputs) blocks until done.

        The scan variant comes from the *plan* (a tiles plan carries its
        tile queue), so plans stay executable even if `self.scan` changes.
        """
        args, static, query_bound = self._step_inputs(plan, k)
        out_d, out_i, prune_stats = sharded_search(*args, **static)
        return InFlightSearch(
            out_d=out_d, out_i=out_i, plan=plan,
            dev_rows=self.plan_dev_rows(plan),
            prune_stats=prune_stats,
            query_bound=query_bound,
        )

    def compiled_search_text(self, plan: SearchPlan, k: int) -> str:
        """HLO text of the compiled search step `plan` dispatches to.

        Shows what runs on the device: a Pallas kernel compiled for the
        chip appears as a `tpu_custom_call`."""
        args, static, _ = self._step_inputs(plan, k)
        return sharded_search.lower(*args, **static).compile().as_text()

    def _step_inputs(self, plan: SearchPlan, k: int):
        """(device arguments, static kwargs, host query bounds) of the
        `sharded_search` step for `plan` at top-`k`."""
        dev = self._device_put()
        ndev = self.shards.ndev
        spec_dev, spec_rep = self._sharding_specs()
        if plan.scan == "tiles":
            tile_pair, tile_block, tile_row0 = (
                plan.tile_pair, plan.tile_block, plan.tile_row0
            )
        else:  # fixed-width placeholders keep the jit cache key stable
            tile_pair = np.zeros((ndev, 1), np.int32)
            tile_block = np.zeros((ndev, 1), np.int32)
            tile_row0 = np.zeros((ndev, 1), np.int32)
        # bound sentinels (-inf / +inf) run the identical executable
        # unpruned; the warm-start bound is derived here because it
        # depends on the dispatched k (plans are k-agnostic)
        if plan.pair_lb is not None:
            pair_lb = plan.pair_lb
        else:
            pair_lb = np.full(
                (ndev, plan.pairs_per_dev), -np.inf, np.float32
            )
        query_bound = plan.query_bounds(k)
        batch = jax.device_put(
            (
                plan.qmc_pairs, plan.pair_q, plan.pair_slot, plan.pair_valid,
                plan.query_pairs, tile_pair, tile_block, tile_row0, pair_lb,
                query_bound,
            ),
            (spec_dev,) * 9 + (spec_rep,),
        )
        static = dict(
            mesh=self.mesh,
            n_queries=plan.n_queries,
            k=k,
            block_n=self.shards.block_n,
            window=self.shards.window,
            path=self.path,
            add_offsets=self.shards.add_offsets,
            scan=plan.scan,
            interpret=self.interpret,
        )
        return (*dev, *batch), static, query_bound

    def _raw_device_put(self):
        """Shard the raw-vector store over the mesh once, cache on device.

        The storage cast (f32 host copy -> `raw.dtype` device copy) happens
        here, so a bf16 store ships half the bytes."""
        if self._raw_arrays is not None:
            return self._raw_arrays
        if self.raw is None:
            raise ValueError(
                "rerank='exact' needs a raw-vector store: build with "
                "store_raw=True (default when rerank='exact') or call "
                "attach_raw_store(xs)"
            )
        spec_dev, spec_rep = self._sharding_specs()
        r = self.raw
        vecs = r.vectors
        if r.dtype == "bfloat16":
            vecs = vecs.astype(jnp.bfloat16)
        self._raw_arrays = jax.device_put(
            (vecs, r.id_dev, r.id_row), (spec_dev, spec_rep, spec_rep)
        )
        return self._raw_arrays

    def dispatch_rerank(
        self, handle: InFlightSearch, queries: np.ndarray, k_out: int
    ) -> InFlightSearch:
        """Chain the exact re-rank stage onto an in-flight ADC search.

        Stays asynchronous: `handle.out_i` (the overfetched ADC candidate
        ids) feeds `sharded_rerank` without a host round-trip, and the
        returned handle's outputs are the re-ranked (exact-f32, tie-stable)
        top-`k_out`.  `queries` must be the original-space queries — the
        raw shard is never rotated (see `schedule_batch`).
        """
        with self.tracer.span("rerank_dispatch", root=False, k_out=k_out):
            raw_dev = self._raw_device_put()
            _, spec_rep = self._sharding_specs()
            q = jax.device_put(np.asarray(queries, np.float32), spec_rep)
            # the ADC kernels pad past-the-end lanes with (+inf, <junk id>);
            # harmless under ADC ordering (inf sorts last) but the re-rank
            # re-scores by exact distance, so junk ids must be masked out or
            # they resurrect as duplicates of real candidates
            cand = jnp.where(jnp.isfinite(handle.out_d), handle.out_i, -1)
            out_d, out_i = sharded_rerank(
                *raw_dev, q, cand,
                mesh=self.mesh, k_out=k_out, block_k=self.rerank_block,
                interpret=self.interpret,
            )
        return dataclasses.replace(handle, out_d=out_d, out_i=out_i)

    def collect(
        self, handle: InFlightSearch
    ) -> tuple[np.ndarray, np.ndarray]:
        """Block until a dispatched step finishes; materialize its results."""
        return np.asarray(handle.out_d), np.asarray(handle.out_i)

    def execute_plan(
        self, plan: SearchPlan, k: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Device-side online phase: dispatch one jitted shard_map step and
        block on its results (the synchronous composition of `dispatch_plan`
        + `collect`)."""
        return self.collect(self.dispatch_plan(plan, k))

    def scanned_rows(self, plan: SearchPlan) -> int:
        """Total code rows DMA'd by one execution of `plan` (all devices).

        The windows path streams pairs_per_dev * window rows per device
        regardless of cluster sizes; the tiles path streams one block per
        emitted tile (dummy padding tiles included), i.e. ~sum(actual
        probed rows) rounded up to the tile bucket.
        """
        ndev = self.shards.ndev
        if plan.scan == "tiles":
            return ndev * plan.tiles_per_dev * self.shards.block_n
        return ndev * plan.pairs_per_dev * self.shards.window

    def search(
        self,
        queries: np.ndarray,
        nprobe: int,
        k: int,
        pairs_per_dev: int | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Full online path.  Returns (dists (Q, k), ids (Q, k)).

        With an active mutation layer (buffered inserts or tombstones) the
        main-path results are overfetched/filtered and merged with the
        delta-buffer top-k; otherwise this is the plain immutable path.
        With `rerank="exact"` both paths run the cascade: the ADC scan
        overfetches `k_prime(k)` candidates and the re-rank stage
        re-selects the top-k by exact f32 distance (distances returned are
        then exact, not quantized).
        """
        if self.mutation_active:
            from repro.retrieval.mutation import mutable_search

            return mutable_search(
                self, queries, nprobe, k, pairs_per_dev=pairs_per_dev
            )
        plan = self.plan_batch(queries, nprobe, pairs_per_dev=pairs_per_dev)
        if self.rerank == "exact":
            kp = self.k_prime(k)
            handle = self.dispatch_plan(plan, kp)
            handle = self.dispatch_rerank(handle, queries, k)
            return self.collect(handle)
        return self.execute_plan(plan, k)
