"""Batched serving on top of MemANNSEngine: micro-batching + shape buckets
+ a double-buffered host/device pipeline with load feedback.

`sharded_search` is jitted with static (n_queries, pairs_per_dev, k, ...),
so naive per-request calls recompile whenever the batch shape drifts.  The
serving layer removes that hazard:

  * incoming queries are grouped into fixed-size micro-batches (ragged tails
    padded with a copy of the first query and sliced off the results, so
    padding never changes any real query's top-k);
  * per-device pair capacities are rounded up to power-of-two *buckets*
    (`round_capacity`), and `warmup()` executes one dummy search per bucket
    so every steady-state batch hits an already-compiled executable;
  * micro-batches flow through a depth-`pipeline_depth` in-flight queue:
    batch i is *dispatched* (async shard_map step) and batch i+1 is planned
    on the host while the device still executes batch i, so host planning
    drops out of the serving critical path (depth 0 restores the strictly
    serial plan -> execute -> block loop);
  * each dispatched plan's per-device rows-scanned report is folded into an
    EWMA `load_carry` that biases Algorithm 2 for subsequent batches — the
    paper's dynamic resource management: a device running hot sheds
    multi-replica work to colder replicas, within and across batches;
  * `ServingStats` tracks cold compiles, bucket hits, the host vs device
    time split, the overlap fraction (host planning hidden behind in-flight
    device work), and per-batch latency samples (p50/p99) — the same
    numbers `benchmarks/bench_qps.py` reports.

The load EWMA is updated at *dispatch* time from the plan's host-computed
row counts (rows scanned are a deterministic function of the plan), not at
collect time: that way the carry seen when planning batch i+1 covers
batches 0..i at every pipeline depth, and depth 0 vs depth 1 produce
bit-identical schedules, hence bit-identical results.

With `mutable=True` the engine also serves online corpus mutations
(insert/delete/compact): delta-buffer searches run at plan time with the
batch's tombstone snapshot (so pipeline depths stay result-identical), the
main path is overfetched while tombstones exist, the tombstone filter +
delta merge compose with the top-k at collect time, and compactions
auto-trigger on delta occupancy / tombstone thresholds.  `warmup()` warms
the overfetched executables and the jitted delta search too, so steady
state never recompiles during churn.

Every engine feature composes here: co-occ encoded shards serve churn like
plain ones (the compiled-shape key already covers the stored width and
dtype, and mutable cooc builds reserve the full plain width, so compaction
re-encoding never changes a warmed shape), pruning and the exact re-rank
cascade stack on top — `tests/test_feature_matrix.py` pins the full
scan × cooc × mutable × prune × rerank matrix at zero steady-state
recompiles.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time

import numpy as np

from repro.core.delta import merge_results
from repro.kernels import ops
from repro.obs.metrics import (
    NULL_REGISTRY,
    PROCESS_REGISTRY,
    MetricsRegistry,
    NullRegistry,
)
from repro.obs.trace import NULL_SPAN, NULL_TRACER
from repro.retrieval.engine import MemANNSEngine, SearchPlan, round_capacity
from repro.retrieval.faults import DeviceHang, FaultError, TransientFault
from repro.retrieval.mutation import (
    compact_engine,
    delete_from,
    delta_exact_rerank,
    delta_prune_bound,
    engine_delta_topk,
    ensure_delta,
    insert_into,
)
from repro.retrieval.search import (
    InFlightSearch,
    rerank_static_key,
    search_static_key,
)


# per-batch lifecycle phases the serving layer times (the `phase` label
# of `upanns_phase_seconds`; eagerly registered so exposition is
# deterministic).  `plan` and `delta` are host work, `dispatch` is the
# async enqueue, `dispatch_wait` is the time a dispatched batch sat
# behind earlier in-flight batches before collect began, `collect_wait`
# is the blocked collect itself (residual device execution + transfer).
PHASES = ("plan", "delta", "dispatch", "dispatch_wait", "collect_wait")

# why a query can come back degraded (the `reason` label of
# `upanns_degraded_queries_total`): "coverage" = some probed cluster had
# no surviving replica (replica failover exhausted), "deadline" = the
# batch ran late and was served at reduced effort instead of missing SLO.
DEGRADE_REASONS = ("coverage", "deadline")

# lifecycle points where a transient fault can be retried (the `phase`
# label of `upanns_retries_total`).
RETRY_PHASES = ("dispatch", "collect")

# health states /healthz reports, in degradation order: "ok" (all devices
# live, queue has room), "degraded" (a device is down or deadlines forced
# degraded service), "overloaded" (ingress queue full; admission control
# is shedding).
HEALTH_STATES = ("ok", "degraded", "overloaded")

# what a grid step of the scan kernel serves (the `kind` label of
# `upanns_scan_steps_total`, `MemANNSEngine.plan_scan_steps`): a real
# query's tile, a padding row's tile, or a capacity dummy
SCAN_STEP_KINDS = ("query", "pad", "bucket")


@dataclasses.dataclass
class ServingStats:
    """Counters accumulated across `ServingEngine` batches.

    This is the single place every field is documented; the serving layer,
    `benchmarks/bench_qps.py` / `bench_pipeline.py` / `bench_mutation.py`,
    and `launch/serve.py` all report subsets of these.

    Throughput / pipeline:
      batches: micro-batches collected.
      queries: real (unpadded) queries served.
      compiles: searches that hit a non-warmed (cold) executable shape —
        the zero-steady-state-recompile contract is `compiles == 0` after
        `warmup()` for any in-config traffic.  Covers the main scan, the
        delta scan, and the re-rank stage (each has its own cache key).
      host_s: host-side planning seconds (cluster filter + Algorithm 2 +
        densify + plan-time delta scans).
      device_s: dispatch + blocked-collect seconds (incl. transfers).
      overlap_s: host planning seconds spent while a batch was in flight —
        planning hidden behind device work by the pipeline.
      dispatch_wait_s: seconds dispatched batches spent queued behind
        earlier in-flight batches before their collect began (pipeline
        depth >= 1 only; part of the end-to-end latency that is NOT this
        batch's own host or device time).
      collect_wait_s: seconds spent blocked inside collect (residual
        device execution + result transfer) — the honest device-side
        component of per-batch latency under pipelining.
      bucket_hits: {pairs_per_dev bucket: times dispatched} histogram.
      registry: the `repro.obs.metrics.MetricsRegistry` every counter
        above is mirrored into (machine-readable: Prometheus text via
        `render_prometheus`, JSON via `snapshot`).  Pass
        `repro.obs.metrics.NULL_REGISTRY` (or construct the serving layer
        with `metrics=False`) to disable.  The full metric catalog lives
        in docs/OBSERVABILITY.md and is drift-checked by
        tools/check_metrics.py.  Percentiles (`latency_percentile`,
        `prune_percentile`, `p50_s`/`p99_s`/`p999_s`) read its
        log-bucketed histograms, and read 0.0 with metrics off.  Some
        families live in the registry only, with no field here:
        `upanns_scan_steps_total` (the scan kernel's grid steps by what
        they serve), which is also added to
        `repro.obs.metrics.PROCESS_REGISTRY` unless metrics are off.

    Scan / early-pruning telemetry:
      rows_scanned: total code rows visited by collected batches.
      tiles_dispatched: real code tiles handed to the kernels, padding
        rows' tiles included (`upanns_scan_steps_total` splits them).
      tiles_skipped: tile bodies the pruning-bound check skipped whole.
      rows_pruned: valid rows inside those skipped tiles.
      warm_bound_queries: real queries dispatched with a finite warm-start
        bound (the bound-availability gauge).

    Re-rank cascade (rerank="exact" only):
      reranked_queries: real queries whose results went through the exact
        re-rank stage.
      rerank_candidates: total overfetched candidates re-scored at full
        precision (reranked_queries × the serving k' bucket).

    Mutation (mutable serving only):
      inserts: vectors appended to the delta buffer.
      deletes: ids tombstoned.
      compactions: delta→main merges triggered (auto or explicit).
      starved_batches: batches where tombstones ate some query's whole
        overfetch window (results truncated once; triggers compaction).
      delta_occupancy: delta buffer fill fraction (gauge, last mutation).
      tombstones: live tombstone count (gauge, last mutation).
      compaction_s: per-compaction latency seconds (feeds
        `compaction_mean_s`).

    Fault tolerance (populated under injected or real faults only):
      failovers: devices marked dead (fault-plan death, exhausted dispatch
        retries, or a hung collect) — each re-routes its replicas' work.
      degraded_queries: queries answered best-effort instead of exactly
        (unreachable probed clusters, or deadline-forced reduced effort).
      rejected_queries: queries shed by admission control (bounded ingress
        queue full; shed, don't stall).
      retries: transient-fault retries (dispatch backoff + collect
        refires) before any escalation.
    """

    batches: int = 0
    queries: int = 0
    compiles: int = 0
    host_s: float = 0.0
    device_s: float = 0.0
    overlap_s: float = 0.0
    dispatch_wait_s: float = 0.0
    collect_wait_s: float = 0.0
    rows_scanned: int = 0
    tiles_dispatched: int = 0
    tiles_skipped: int = 0
    rows_pruned: int = 0
    warm_bound_queries: int = 0
    reranked_queries: int = 0
    rerank_candidates: int = 0
    inserts: int = 0
    deletes: int = 0
    compactions: int = 0
    starved_batches: int = 0
    failovers: int = 0
    degraded_queries: int = 0
    rejected_queries: int = 0
    retries: int = 0
    delta_occupancy: float = 0.0
    tombstones: int = 0
    compaction_s: list[float] = dataclasses.field(default_factory=list)
    bucket_hits: dict[int, int] = dataclasses.field(default_factory=dict)
    registry: object = None

    def __post_init__(self):
        if self.registry is None:
            self.registry = MetricsRegistry()
        r = self.registry
        # the full catalog registers up front so exposition (and the
        # tools/check_metrics.py drift check against docs/OBSERVABILITY.md)
        # is deterministic regardless of which paths traffic exercised
        self.m_batches = r.counter(
            "upanns_serving_batches_total",
            "Micro-batches collected, by scan variant", ("scan",))
        self.m_queries = r.counter(
            "upanns_serving_queries_total",
            "Real (unpadded) queries served")
        self.m_compiles = r.counter(
            "upanns_serving_compiles_total",
            "Cold executable compiles (0 after warmup is the contract)")
        self.m_host = r.counter(
            "upanns_host_seconds_total",
            "Host-side planning seconds (cluster filter + Algorithm 2 + "
            "densify + plan-time delta scans)")
        self.m_device = r.counter(
            "upanns_device_seconds_total",
            "Dispatch + blocked-collect seconds (incl. transfers)")
        self.m_overlap = r.counter(
            "upanns_overlap_seconds_total",
            "Host planning seconds hidden behind in-flight device work")
        self.m_latency = r.histogram(
            "upanns_batch_latency_seconds",
            "Per-micro-batch plan->collect latency")
        self.m_phase = r.histogram(
            "upanns_phase_seconds",
            "Per-micro-batch seconds by lifecycle phase", ("phase",))
        for p in PHASES:  # eager children: exposition order is stable
            self.m_phase.labels(phase=p)
        self.m_rows_scanned = r.counter(
            "upanns_rows_scanned_total",
            "Code rows visited, per device", ("device",))
        self.m_tiles_dispatched = r.counter(
            "upanns_tiles_dispatched_total",
            "Real code tiles handed to the kernels, padding rows' included")
        steps = ("upanns_scan_steps_total",
                 "Scan kernel grid steps, by what they serve", ("kind",))
        self.m_scan_steps = r.counter(*steps)
        # its process-lifetime twin, which outlives this engine
        self.m_scan_steps_process = (
            NULL_REGISTRY if isinstance(r, NullRegistry)
            else PROCESS_REGISTRY).counter(*steps)
        for kind in SCAN_STEP_KINDS:  # eager: exposition order is stable
            self.m_scan_steps.labels(kind=kind)
            self.m_scan_steps_process.labels(kind=kind)
        self.m_tiles_skipped = r.counter(
            "upanns_tiles_skipped_total",
            "Tile bodies the pruning-bound check skipped whole, per device",
            ("device",))
        self.m_rows_pruned = r.counter(
            "upanns_rows_pruned_total",
            "Valid rows inside skipped tiles, per device", ("device",))
        self.m_prune_frac = r.histogram(
            "upanns_prune_fraction",
            "Per-batch skipped/dispatched tile fraction")
        self.m_warm_bound = r.counter(
            "upanns_warm_bound_queries_total",
            "Real queries dispatched with a finite warm-start bound")
        self.m_bucket_hits = r.counter(
            "upanns_bucket_hits_total",
            "Dispatches per pairs-per-device capacity bucket", ("bucket",))
        self.m_rerank_queries = r.counter(
            "upanns_rerank_queries_total",
            "Queries re-scored by the exact cascade", ("rerank",))
        self.m_rerank_candidates = r.counter(
            "upanns_rerank_candidates_total",
            "Overfetched candidates re-scored at full precision", ("rerank",))
        self.m_inserts = r.counter(
            "upanns_mutation_inserts_total",
            "Vectors appended to the delta buffer")
        self.m_deletes = r.counter(
            "upanns_mutation_deletes_total", "Ids tombstoned")
        self.m_compactions = r.counter(
            "upanns_compactions_total",
            "Delta->main merges triggered (auto or explicit)")
        self.m_starved = r.counter(
            "upanns_starved_batches_total",
            "Batches where tombstones ate a query's whole overfetch window")
        self.m_delta_occupancy = r.gauge(
            "upanns_delta_occupancy", "Delta buffer fill fraction")
        self.m_tombstones = r.gauge(
            "upanns_tombstones", "Live tombstone count")
        self.m_compaction_s = r.histogram(
            "upanns_compaction_seconds", "Per-compaction latency")
        self.m_failovers = r.counter(
            "upanns_failovers_total",
            "Devices failed over (death, exhausted retries, hung collect), "
            "per device", ("device",))
        self.m_degraded = r.counter(
            "upanns_degraded_queries_total",
            "Queries answered best-effort, by degradation reason",
            ("reason",))
        for reason in DEGRADE_REASONS:  # eager: exposition order is stable
            self.m_degraded.labels(reason=reason)
        self.m_rejected = r.counter(
            "upanns_rejected_queries_total",
            "Queries shed by admission control (ingress queue full)")
        self.m_retries = r.counter(
            "upanns_retries_total",
            "Transient-fault retries before escalation, by phase",
            ("phase",))
        for p in RETRY_PHASES:
            self.m_retries.labels(phase=p)
        self.m_device_health = r.gauge(
            "upanns_device_health",
            "Per-device liveness (1 live, 0 failed over)", ("device",))
        self.m_queue_depth = r.gauge(
            "upanns_queue_depth",
            "Queries pending in the ingress queue (admission control)")

    # -------------------- recording helpers --------------------------- #
    # Each helper updates the legacy field AND its registry mirror, so the
    # two can never drift; serving code calls these instead of touching
    # either store directly.

    def note_scan_steps(self, steps: tuple[int, int, int]) -> None:
        """One batch's scan grid steps (`SCAN_STEP_KINDS` order), into
        the engine's registry and the process-lifetime one."""
        for kind, n in zip(SCAN_STEP_KINDS, steps):
            self.m_scan_steps.inc(n, kind=kind)
            self.m_scan_steps_process.inc(n, kind=kind)

    def note_compile(self) -> None:
        self.compiles += 1
        self.m_compiles.inc()

    def note_bucket_hit(self, bucket: int) -> None:
        self.bucket_hits[bucket] = self.bucket_hits.get(bucket, 0) + 1
        self.m_bucket_hits.inc(bucket=bucket)

    def note_host(self, seconds: float, overlapped: bool) -> None:
        self.host_s += seconds
        self.m_host.inc(seconds)
        if overlapped:
            self.overlap_s += seconds
            self.m_overlap.inc(seconds)

    def observe_phase(self, phase: str, seconds: float) -> None:
        self.m_phase.observe(seconds, phase=phase)

    def note_inserts(self, n: int) -> None:
        self.inserts += n
        self.m_inserts.inc(n)

    def note_deletes(self, n: int) -> None:
        self.deletes += n
        self.m_deletes.inc(n)

    def note_compaction(self, latency_s: float) -> None:
        self.compactions += 1
        self.compaction_s.append(latency_s)
        self.m_compactions.inc()
        self.m_compaction_s.observe(latency_s)

    def set_mutation_gauges(self, occupancy: float, tombstones: int) -> None:
        self.delta_occupancy = occupancy
        self.tombstones = tombstones
        self.m_delta_occupancy.set(occupancy)
        self.m_tombstones.set(tombstones)

    def note_failover(self, device: int) -> None:
        self.failovers += 1
        self.m_failovers.inc(device=int(device))

    def note_degraded(self, n: int, reason: str) -> None:
        self.degraded_queries += n
        self.m_degraded.inc(n, reason=reason)

    def note_rejected(self, n: int) -> None:
        self.rejected_queries += n
        self.m_rejected.inc(n)

    def note_retry(self, phase: str) -> None:
        self.retries += 1
        self.m_retries.inc(phase=phase)

    def set_device_health(self, device: int, live: bool) -> None:
        self.m_device_health.set(1.0 if live else 0.0, device=int(device))

    def set_queue_depth(self, depth: int) -> None:
        self.m_queue_depth.set(depth)

    def snapshot(self) -> dict:
        """JSON-able dump of every registered metric (bench row stamp)."""
        return self.registry.snapshot()

    # ------------------------ derived views --------------------------- #

    def host_fraction(self) -> float:
        total = self.host_s + self.device_s
        return self.host_s / total if total > 0 else 0.0

    def prune_fraction(self) -> float:
        """Lifetime fraction of dispatched tile bodies the bounds skipped."""
        if self.tiles_dispatched <= 0:
            return 0.0
        return self.tiles_skipped / self.tiles_dispatched

    def prune_percentile(self, q: float) -> float:
        """Per-batch prune-effectiveness percentile (bound-tightening
        profile).  Histogram-backed (O(1) memory, rel. error <=
        sqrt(GROWTH)-1); 0.0 when metrics are off."""
        return self.m_prune_frac.labels().quantile(q)

    def overlap_fraction(self) -> float:
        """Fraction of host planning time hidden behind in-flight batches."""
        return self.overlap_s / self.host_s if self.host_s > 0 else 0.0

    def latency_percentile(self, q: float) -> float:
        """Per-micro-batch latency percentile in seconds (plan -> collect).

        Backed by the `upanns_batch_latency_seconds` log-bucketed histogram
        (lifetime, O(1) memory, relative error <= sqrt(GROWTH)-1 ~ 4.4%,
        p999 as cheap as p50); 0.0 when metrics are off."""
        return self.m_latency.labels().quantile(q)

    def phase_percentile(self, phase: str, q: float) -> float:
        """Per-batch percentile of one lifecycle phase (see `PHASES`)."""
        return self.m_phase.labels(phase=phase).quantile(q)

    def phase_seconds(self, phase: str) -> float:
        """Total seconds spent in one lifecycle phase (see `PHASES`)."""
        return float(self.m_phase.labels(phase=phase).sum)

    def p50_s(self) -> float:
        return self.latency_percentile(50.0)

    def p99_s(self) -> float:
        return self.latency_percentile(99.0)

    def p999_s(self) -> float:
        """p999 latency — free with the histogram backend (and exactly as
        trustworthy as p50: same bounded relative error)."""
        return self.latency_percentile(99.9)

    def compaction_mean_s(self) -> float:
        if not self.compaction_s:
            return 0.0
        return float(np.mean(self.compaction_s))


@dataclasses.dataclass
class ServingResult:
    """One `ServingEngine.search_result` answer with degradation accounting.

    `search()` returns just (dists, ids); this carries the honest
    coverage story alongside.  A query is *degraded* when its answer may
    differ from the fault-free one — either some probed cluster had no
    surviving replica ("coverage") or its batch ran past the deadline and
    was served at reduced effort ("deadline").  Non-degraded queries are
    bit-identical to the no-fault run (pinned by tests/test_faults.py).

    Attributes:
      dists: (Q, k) f32 distances (best-effort top-k for degraded rows).
      ids: (Q, k) int32 global ids.
      degraded: (Q,) bool — degraded for ANY reason.
      deadline_degraded: (Q,) bool — served late at reduced effort.
      coverage_lost: (L, 2) int32 [query, cluster] pairs whose cluster was
        unreachable (every replica dead) — exactly the clusters missing
        from those queries' scans, the honest coverage accounting.
    """

    dists: np.ndarray
    ids: np.ndarray
    degraded: np.ndarray
    deadline_degraded: np.ndarray
    coverage_lost: np.ndarray

    def coverage_degraded(self) -> np.ndarray:
        """(Q,) bool — queries with at least one unreachable cluster."""
        mask = np.zeros(self.dists.shape[0], bool)
        if self.coverage_lost.size:
            mask[self.coverage_lost[:, 0]] = True
        return mask


@dataclasses.dataclass
class _Flight:
    """One in-flight micro-batch plus everything needed to refire it.

    The retry/failover layer needs more than the legacy inflight tuple:
    a hung collect replans the SAME padded queries (with the shrunken
    live-device set and the same effective nprobe) and re-dispatches, and
    the plan-time mutation snapshot is reused so the refired batch sees
    the corpus state its stream position promised.
    """

    handle: InFlightSearch | None
    q_n: int                 # real (unpadded) queries in this chunk
    offset: int              # chunk start within the search() query array
    t_start: float
    mut: tuple | None
    t_dispatched: float | None
    bspan: object
    seq: int                 # global micro-batch sequence number
    padded: np.ndarray       # padded queries (refire input)
    nprobe_eff: int          # nprobe this batch was planned with
    k_fetch: int
    skip_rerank: bool        # deadline-degraded: cascade skipped
    deadline_late: bool
    # the plan's scan grid steps (query, pad, bucket), for the counter
    steps: tuple[int, int, int] = (0, 0, 0)


class ServingEngine:
    """Steady-state serving wrapper around one `MemANNSEngine`.

    Args:
      engine: built MemANNSEngine.
      nprobe: clusters probed per query (fixed per serving config).
      k: neighbours returned per query.
      micro_batch: queries per shard_map step; requests are padded/split to
        this size so `n_queries` stays static.
      capacity_floor: smallest pairs-per-device bucket.
      pipeline_depth: max in-flight micro-batches; 1 (default) overlaps
        host planning of batch i+1 with device execution of batch i, 0 is
        the strictly serial loop.  Results are bit-identical across depths.
      load_feedback: feed the per-device rows-scanned EWMA back into
        Algorithm 2 as `load_carry` (the paper's dynamic resource manager);
        off reproduces the static, load-blind scheduler.
      load_alpha: EWMA smoothing factor for the load carry (1.0 = last
        batch only).
      mutable: enable the online mutation path (insert/delete/compact):
        the engine's delta buffer is allocated, `warmup()` additionally
        warms the overfetched main-path executables and the jitted delta
        search, and mutations auto-compact at the thresholds below.
      compact_occupancy: auto-compact when the delta buffer fill fraction
        reaches this.
      tombstone_limit: auto-compact when this many ids are tombstoned
        (default delta_capacity // 4).
      overfetch: extra main-path results fetched while tombstones exist
        (default k, i.e. fetch 2k), absorbing up to `overfetch` filtered
        rows per query.  A query whose whole fetch window is tombstoned
        returns truncated ((+inf, -1)-padded) rows once; that batch is
        counted in `stats.starved_batches` and triggers an immediate
        compaction, so the next search is exact again.
      replace_threshold: relative cluster-size change beyond which a
        compaction re-places the cluster via Algorithm 1.
      delta_capacity: initial delta-buffer rows (pow2-bucketed; growth
        beyond a warmed bucket is an honest cold compile).
      autotune: kernel-geometry autotuning mode, resolved once at
        `warmup()` (see `repro.core.autotune`): "off" serves the engine's
        build-time geometry untouched; "cache" (default) applies the
        cached measured geometry for this (backend, shard shape, k) if one
        exists, else the in-repo per-backend default; "sweep" measures a
        candidate grid on synthetic shards first and persists the winner,
        so later processes hit the cache.  Applying a different `block_n`
        retiles the shards (bit-identical results by construction); the
        warm set is computed AFTER the geometry lands, so tuned serving
        keeps the zero-steady-state-recompile contract.
      autotune_cache_dir: override the autotune cache directory
        (default `~/.cache/repro`); tests and CI point this at a tmpdir.
      metrics: mirror `ServingStats` into a per-engine
        `repro.obs.metrics.MetricsRegistry` (`stats.registry`): Prometheus
        text / JSON exposition, histogram-backed p50/p99/p999.  `False`
        installs `NULL_REGISTRY` (every mirror call a no-op) and the
        percentile estimators read 0.0.
      deadline_ms: per-search() latency budget in milliseconds (None =
        no deadline).  Micro-batches planned after the budget has elapsed
        are served DEGRADED — nprobe shrinks to `degrade_nprobe` and an
        immutable exact-rerank cascade is skipped (ADC distances) — rather
        than making every later batch miss the SLO harder.  Degraded
        batches are flagged per query (`ServingResult.deadline_degraded`)
        and counted under `upanns_degraded_queries_total{reason="deadline"}`.
        `warmup()` additionally warms the degraded shapes, so deadline
        degradation never compiles in steady state.
      degrade_nprobe: nprobe served to deadline-degraded batches
        (default max(1, nprobe // 2); must be in [1, nprobe]).
      retry_limit: transient dispatch failures retried per batch before
        escalating (capped exponential backoff between attempts).
      retry_backoff_s: first retry backoff; doubles per attempt, capped
        at `retry_backoff_max_s`.
      queue_limit: admission control — max queries held in the ingress
        queue (`submit`).  Beyond it, submissions are REJECTED (counted,
        `submit` returns the accepted count) instead of growing the queue
        without bound; `health()` reports "overloaded" while full.
        None = unbounded (legacy behavior).
      collect_timeout_s: watchdog for the silent-stall hazard: a collect
        that is not ready within this many seconds raises a fault event
        (an attributed hang fails the device over and the batch refires
        on the survivors) instead of blocking the serving loop forever.
        None = blocking collect (legacy).  The watchdog polls
        `InFlightSearch.is_ready`, so the healthy path's phase accounting
        is unchanged when it never fires.
      faults: optional `repro.retrieval.faults.FaultPlan` injecting
        deterministic faults (device death, transient dispatch errors,
        hung/slow collects) — the test/benchmark harness for everything
        above.  None (production) skips every hook.
      tracer: a `repro.obs.trace.Tracer` recording one span tree per
        micro-batch (plan > schedule/densify/emit_tiles, delta, dispatch >
        rerank_dispatch, dispatch_wait, collect, merge; compactions root
        their own tree).  Installed on the engine too, so engine-level
        sub-phases nest under the serving spans.  `None` (default) traces
        nothing at zero cost.  Tracing and metrics are observability,
        never behavior: results are bit-identical and steady-state
        compiles stay 0 with them on or off (pinned by tests/test_obs.py).

    The re-rank cascade is configured on the ENGINE (`rerank="exact"` +
    `k_overfetch`), not here: serving reads `engine.rerank` and serves
    the cascade through one fixed fetch bucket (`_k_fetch`) so mutation
    state never shifts executable shapes; `warmup()` then chains the
    re-rank executable (and, mutable, the host delta re-rank kernel) into
    the warmed set, keeping `stats.compiles == 0` in steady state.
    """

    def __init__(
        self,
        engine: MemANNSEngine,
        *,
        nprobe: int,
        k: int,
        micro_batch: int = 32,
        capacity_floor: int = 8,
        pipeline_depth: int = 1,
        load_feedback: bool = True,
        load_alpha: float = 0.5,
        mutable: bool = False,
        compact_occupancy: float = 0.75,
        tombstone_limit: int | None = None,
        overfetch: int | None = None,
        replace_threshold: float = 0.25,
        delta_capacity: int = 4096,
        autotune: str = "cache",
        autotune_cache_dir: str | None = None,
        metrics: bool = True,
        tracer=None,
        deadline_ms: float | None = None,
        degrade_nprobe: int | None = None,
        retry_limit: int = 2,
        retry_backoff_s: float = 0.05,
        retry_backoff_max_s: float = 1.0,
        queue_limit: int | None = None,
        collect_timeout_s: float | None = None,
        faults=None,
    ):
        if autotune not in ("off", "cache", "sweep"):
            raise ValueError(
                f"autotune must be 'off', 'cache' or 'sweep', got {autotune!r}"
            )
        self.engine = engine
        self.nprobe = int(nprobe)
        self.k = int(k)
        self.micro_batch = int(micro_batch)
        self.capacity_floor = int(capacity_floor)
        self.pipeline_depth = int(pipeline_depth)
        self.load_feedback = bool(load_feedback)
        self.load_alpha = float(load_alpha)
        self.mutable = bool(mutable) or engine.delta is not None
        self.compact_occupancy = float(compact_occupancy)
        self.overfetch = int(overfetch) if overfetch is not None else int(k)
        self.replace_threshold = float(replace_threshold)
        self.autotune = autotune
        self.autotune_cache_dir = autotune_cache_dir
        self.autotune_report: dict | None = None
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if tracer is not None:
            # engine-level sub-phase spans (schedule/densify/emit_tiles,
            # rerank_dispatch, compaction internals) nest under ours
            engine.tracer = tracer
        self.stats = ServingStats(
            registry=MetricsRegistry() if metrics else NULL_REGISTRY
        )
        self.deadline_ms = (
            float(deadline_ms) if deadline_ms is not None else None
        )
        self.degrade_nprobe = (
            int(degrade_nprobe)
            if degrade_nprobe is not None
            else max(1, self.nprobe // 2)
        )
        if not 1 <= self.degrade_nprobe <= self.nprobe:
            raise ValueError(
                f"degrade_nprobe {self.degrade_nprobe} not in "
                f"[1, {self.nprobe}]"
            )
        self.retry_limit = int(retry_limit)
        self.retry_backoff_s = float(retry_backoff_s)
        self.retry_backoff_max_s = float(retry_backoff_max_s)
        self.queue_limit = (
            int(queue_limit) if queue_limit is not None else None
        )
        self.collect_timeout_s = (
            float(collect_timeout_s) if collect_timeout_s is not None
            else None
        )
        self.faults = faults
        self._warm: set[tuple] = set()
        self._pending: list[np.ndarray] = []
        self._starved = False
        self._load_ewma = np.zeros(engine.shards.ndev, np.float64)
        self._live = np.ones(engine.shards.ndev, bool)
        self._batch_seq = 0
        self._deadline_hit = False
        for dev in range(engine.shards.ndev):  # eager health gauges
            self.stats.set_device_health(dev, True)
        if self.mutable:
            ensure_delta(engine, delta_capacity)
        self.tombstone_limit = (
            int(tombstone_limit)
            if tombstone_limit is not None
            else max(64, (engine.delta.capacity if engine.delta else delta_capacity) // 4)
        )

    # ------------------------------------------------------------------ #

    def _key(self, plan: SearchPlan, k: int | None = None) -> tuple:
        """jit-cache key of the executable `plan` dispatches to.

        Keyed on the *plan's* scan variant (`execute_plan`/`dispatch_plan`
        honor `plan.scan`, not `engine.scan`), so flipping `engine.scan`
        after warmup can neither miscount compiles nor mark the wrong
        executable warm.  The shard array shapes are appended: a compaction
        that grew the packed storage changes the executable even though
        every static arg stayed equal, and the compile counter must see it.
        """
        s = self.engine.shards
        return search_static_key(
            ndev=s.ndev,
            n_queries=plan.n_queries,
            pairs_per_dev=plan.pairs_per_dev,
            k=self.k if k is None else k,
            block_n=s.block_n,
            window=s.window,
            path=self.engine.path,
            add_offsets=s.add_offsets,
            scan=plan.scan,
            tiles_per_dev=plan.tiles_per_dev,
            query_width=plan.query_pairs.shape[2],
        ) + (s.codes.shape, s.slot_start.shape[1])

    def _delta_key(self) -> tuple:
        """Compile-cache key of the jitted delta search for this config."""
        d = self.engine.delta
        return (
            "delta", self.micro_batch, d.capacity, self.nprobe,
            self._delta_k(), self.engine.rerank,
        )

    def _delta_k(self) -> int:
        """Rows fetched from the delta scan per query (the jitted k)."""
        if self.engine.rerank == "exact":
            d = self.engine.delta
            cap = d.capacity if d is not None else self._k_fetch()
            return min(self._k_fetch(), cap)
        return self.k

    def _rerank_key(self, k_cand: int, k_out: int) -> tuple:
        """Compile-cache key of the re-rank executable for this config."""
        r = self.engine.raw
        return rerank_static_key(
            ndev=self.engine.shards.ndev,
            n_queries=self.micro_batch,
            k_cand=k_cand,
            k_out=k_out,
            dim=r.dim,
            row_capacity=r.row_capacity,
            ids_capacity=r.ids_capacity,
            dtype=r.dtype,
            block_k=self.engine.rerank_block,
        )

    def _k_fetch(self) -> int:
        """Main-path fetch size for this serving config.

        Plain path: `k`, widened to `k + overfetch` while tombstones exist
        so the collect-time filter can absorb up to `overfetch` dead rows
        per query (starvation beyond that triggers a compaction; see
        search).

        Cascade path (rerank="exact"): ONE fixed pow2 bucket for the whole
        stream — `k'` when immutable, `round_capacity(k' + overfetch)`
        when mutable (tombstone headroom included up front) — so mutation
        state never shifts the executable shape mid-stream and the
        compiles==0 contract holds under churn."""
        if self.engine.rerank == "exact":
            kp = self.engine.k_prime(self.k)
            if self.mutable:
                return round_capacity(kp + self.overfetch, floor=kp)
            return kp
        d = self.engine.delta
        if d is not None and d.tombstone_count > 0:
            return self.k + self.overfetch
        return self.k

    def load_carry(self) -> np.ndarray:
        """Current (ndev,) EWMA of per-device rows scanned (a copy)."""
        return self._load_ewma.copy()

    def default_buckets(self, nprobe: int | None = None) -> list[int]:
        """Power-of-two capacities from the balanced share to the worst case.

        A perfectly balanced schedule puts Q*nprobe/ndev pairs on each
        device; the worst case (every probed cluster single-replica on one
        device) is Q*nprobe.  Warming every power of two in between covers
        any schedule this config can produce — including load-biased ones,
        whose per-device counts stay within the same worst case.  The
        worst case also covers failover re-routing: a schedule over fewer
        live devices still assigns at most every pair to one device.

        `nprobe` overrides the serving nprobe (warmup uses it to cover the
        deadline-degraded ladder too).
        """
        total = self.micro_batch * (
            self.nprobe if nprobe is None else nprobe
        )
        ndev = self.engine.shards.ndev
        lo = round_capacity(
            math.ceil(total / ndev), floor=self.capacity_floor
        )
        hi = round_capacity(total, floor=self.capacity_floor)
        return [lo << i for i in range(int(math.log2(hi // lo)) + 1)]

    def tile_buckets(self, pairs_per_dev: int) -> list[int]:
        """Reachable tile capacities for one pair bucket: b, 2b, .., b*wb.

        A pair emits at most window/block_n tiles, so the auto-chosen tile
        capacity (`round_capacity(max_tiles, floor=pairs_per_dev)`) always
        lands on pairs_per_dev * 2^i with 2^i <= pow2(window/block_n);
        warming exactly that ladder covers every schedule this config can
        produce.
        """
        s = self.engine.shards
        wb = max(s.window // s.block_n, 1)
        wb2 = 1 << math.ceil(math.log2(wb))
        return [
            pairs_per_dev << i for i in range(int(math.log2(wb2)) + 1)
        ]

    def _dummy_plan(
        self, pairs_per_dev: int, tiles_per_dev: int = 0
    ) -> SearchPlan:
        """Shape-exact all-invalid plan: compiles without scheduling anything."""
        ndev = self.engine.shards.ndev
        dim = self.engine.index.centroids.shape[1]
        tile_pair = tile_block = tile_row0 = None
        if tiles_per_dev:  # all-dummy tile list (pair id P prunes away)
            tile_pair = np.full(
                (ndev, tiles_per_dev), pairs_per_dev, np.int32
            )
            tile_block = np.zeros((ndev, tiles_per_dev), np.int32)
            tile_row0 = np.zeros((ndev, tiles_per_dev), np.int32)
        return SearchPlan(
            qmc_pairs=np.zeros((ndev, pairs_per_dev, dim), np.float32),
            pair_q=np.zeros((ndev, pairs_per_dev), np.int32),
            pair_slot=np.zeros((ndev, pairs_per_dev), np.int32),
            pair_valid=np.zeros((ndev, pairs_per_dev), bool),
            schedule=None,
            n_queries=self.micro_batch,
            pairs_per_dev=pairs_per_dev,
            query_pairs=np.full(
                (ndev, self.micro_batch, self.nprobe), pairs_per_dev,
                np.int32,
            ),
            tile_pair=tile_pair,
            tile_block=tile_block,
            tile_row0=tile_row0,
            tiles_per_dev=tiles_per_dev,
        )

    def apply_autotune(self) -> dict:
        """Resolve + apply the tuned kernel geometry (once; see `autotune`).

        Called by `warmup()` before any executable is warmed, so warm keys
        are computed against the post-retile shard geometry.  Idempotent:
        the first call resolves via `repro.core.autotune.autotune_engine`
        and applies the pick (`MemANNSEngine.apply_geometry` — retiles on a
        block_n change, bit-identical results); later calls return the
        stored report.
        """
        if self.autotune_report is not None:
            return self.autotune_report
        from repro.core.autotune import autotune_engine

        geo, report = autotune_engine(
            self.engine,
            self.k,
            mode=self.autotune,
            cache_dir=self.autotune_cache_dir,
        )
        if geo is not None:
            report["retiled"] = self.engine.apply_geometry(geo)
        report["applied"] = self.tuned_geometry()
        self.autotune_report = report
        return report

    def tuned_geometry(self) -> dict:
        """The engine's effective kernel geometry (for stats/bench rows)."""
        return self.engine.geometry().as_dict()

    def warmup(self, buckets: list[int] | None = None) -> list[int]:
        """Compile `sharded_search` for every bucket with a dummy batch.

        jit caching is keyed by input shapes + static args, so one
        execution per bucket shape is the warm (the dummy plan marks every
        pair invalid, so nothing is scanned); afterwards any batch whose
        capacity falls in `buckets` runs without compiling.  On the tiles
        scan path each pair bucket is warmed at every reachable tile
        capacity (`tile_buckets`), so steady state never recompiles on
        tile-count drift either.

        The kernel-geometry autotune resolves FIRST (`apply_autotune`):
        any retile lands before the executables compile, so the warmed
        shapes are the tuned shapes.

        With a deadline configured, the degraded shapes are warmed too:
        the `degrade_nprobe` bucket ladder, the plain-k executable a
        deadline-skipped cascade falls back to, and the host planner at
        the degraded nprobe — so deadline degradation (like failover,
        which never changes shapes at all) keeps `compiles == 0`.
        """
        self.apply_autotune()
        buckets = sorted(buckets or self.default_buckets())
        if self.deadline_ms is not None:
            buckets = sorted(
                set(buckets) | set(self.default_buckets(self.degrade_nprobe))
            )
        rerank = self.engine.rerank == "exact"
        # deadline-degraded immutable cascades skip the re-rank stage and
        # serve plain ADC top-k: that executable needs warming as well
        plain_ks = (
            [self.k]
            if rerank and self.deadline_ms is not None and not self.mutable
            else []
        )
        dim = self.engine.index.centroids.shape[1]
        if rerank:
            # the cascade serves one fixed fetch bucket for the whole
            # stream (see _k_fetch), so exactly one (scan k', rerank) pair
            # needs warming per plan bucket
            ks = [self._k_fetch()]
            k_out = self._k_fetch() if self.mutable else self.k
            dummy_q = np.zeros((self.micro_batch, dim), np.float32)
        else:
            # the mutable path additionally needs the overfetched
            # executables (tombstone filtering fetches k + overfetch)
            ks = [self.k] + (
                [self.k + self.overfetch] if self.mutable else []
            )
        for b in buckets:
            tile_caps = (
                self.tile_buckets(b) if self.engine.scan == "tiles" else [0]
            )
            for t in tile_caps:
                plan = self._dummy_plan(b, t)
                for kf in ks:
                    if rerank:
                        handle = self.engine.dispatch_plan(plan, kf)
                        handle = self.engine.dispatch_rerank(
                            handle, dummy_q, k_out
                        )
                        self.engine.collect(handle)
                        self._warm.add(self._rerank_key(kf, k_out))
                    else:
                        self.engine.execute_plan(plan, kf)
                    self._warm.add(self._key(plan, kf))
                for kf in plain_ks:
                    self.engine.execute_plan(plan, kf)
                    self._warm.add(self._key(plan, kf))
        # warm the host path too (filter_clusters jit for this batch shape);
        # auto capacity, so a degenerate dummy schedule can never overflow
        dim = self.engine.index.centroids.shape[1]
        self.engine.plan_batch(
            np.zeros((self.micro_batch, dim), np.float32), self.nprobe
        )
        if self.deadline_ms is not None:
            # the degraded host planner (filter_clusters jits per nprobe)
            self.engine.plan_batch(
                np.zeros((self.micro_batch, dim), np.float32),
                self.degrade_nprobe,
            )
        if self.mutable:
            self._warm_delta()
        return buckets

    def _warm_delta(self) -> None:
        """Compile the delta search for the current capacity bucket."""
        dim = self.engine.index.centroids.shape[1]
        kd = self._delta_k()
        engine_delta_topk(
            self.engine,
            np.zeros((self.micro_batch, dim), np.float32),
            self.nprobe,
            kd,
        )
        if self.engine.rerank == "exact":
            # the delta cascade re-ranks on the host kernel at a fixed
            # (micro_batch, kd, dim) shape — warm that executable too
            ops.rerank_dists(
                np.zeros((self.micro_batch, dim), np.float32),
                np.zeros((self.micro_batch, kd, dim), np.float32),
                block_k=self.engine.rerank_block,
                interpret=self.engine.interpret,
            )
        self._warm.add(self._delta_key())

    # ------------------------------------------------------------------ #

    def _pad_chunk(self, queries: np.ndarray) -> np.ndarray:
        """Pad one chunk to the micro-batch size (rows sliced off later)."""
        q_n = queries.shape[0]
        if q_n < self.micro_batch:  # pad; padded rows sliced off at collect
            pad = np.broadcast_to(
                queries[:1], (self.micro_batch - q_n, queries.shape[1])
            )
            queries = np.concatenate([queries, pad], axis=0)
        return queries

    def _live_arg(self) -> np.ndarray | None:
        """Live mask for the scheduler: None (free) while all devices live."""
        return None if self._live.all() else self._live

    def _plan_micro_batch(
        self, queries: np.ndarray, nprobe: int | None = None
    ) -> SearchPlan:
        """Plan one padded micro-batch (host side).

        `nprobe` overrides the serving nprobe (deadline degradation).  The
        current live-device mask is threaded to Algorithm 2 only when a
        device has failed over, so the healthy path plans bit-identically
        to a fault-unaware engine.
        """
        return self.engine.plan_batch(
            queries,
            self.nprobe if nprobe is None else nprobe,
            capacity_floor=self.capacity_floor,
            load_carry=self._load_ewma if self.load_feedback else None,
            live=self._live_arg(),
            # degraded plans keep the full-nprobe index width: same
            # executables as the healthy path
            query_width=self.nprobe,
        )

    def _delta_micro_batch(
        self, padded: np.ndarray, plan: SearchPlan, k_fetch: int
    ) -> tuple[np.ndarray | None, np.ndarray | None, np.ndarray]:
        """Delta top-k + tombstone snapshot for one padded micro-batch.

        Runs at plan time so mutations landing later in the stream never
        retroactively change an already-planned batch (pipeline-depth
        invariance); returns (delta_d, delta_i, tombstone_array).  The
        delta scan gets the same early-pruning bound semantics as the
        device kernels when it is provably safe (`delta_prune_bound`).
        """
        delta = self.engine.delta
        if delta is None or not delta.active:
            return None, None, np.zeros(0, np.int64)
        tomb = delta.tombstone_array()
        if delta.live_count == 0:
            return None, None, tomb
        key = self._delta_key()
        if key not in self._warm:  # capacity grew past the warmed bucket
            self.stats.note_compile()
            self._warm.add(key)
        if self.engine.rerank == "exact":
            # cascade: the ADC prune bound lives in ADC space and a row
            # above it can still win on exact distance, so the delta scan
            # runs unbounded; candidates are re-ranked on raw delta rows
            kd = self._delta_k()
            dd, di = engine_delta_topk(
                self.engine, padded, self.nprobe, kd, bound=None
            )
            dd, di = delta_exact_rerank(
                delta, padded, dd, di,
                interpret=self.engine.interpret,
                block_k=self.engine.rerank_block,
            )
            return dd, di, tomb
        bound = delta_prune_bound(
            self.engine, plan, self.k, k_fetch, tomb.size
        )
        dd, di = engine_delta_topk(
            self.engine, padded, self.nprobe, self.k, bound=bound
        )
        return dd, di, tomb

    def _dispatch_micro_batch(
        self,
        plan: SearchPlan,
        k_fetch: int | None = None,
        queries: np.ndarray | None = None,
        skip_rerank: bool = False,
    ) -> InFlightSearch:
        """Dispatch a planned micro-batch; update warm/compile + load state.

        The load EWMA folds in this plan's host-computed row counts *now*
        (not at collect) so the carry is identical at every pipeline depth.
        `k_fetch` defaults to the serving k; the mutable path overfetches
        while tombstones exist.  With rerank="exact", `queries` (the padded
        micro-batch) must be passed and the exact re-rank stage is chained
        onto the dispatched scan before the handle returns —
        `skip_rerank=True` (deadline degradation) serves the plain ADC
        top-k instead (`k_fetch` must then be the serving k).
        """
        if k_fetch is None:
            k_fetch = self.k
        key = self._key(plan, k_fetch)
        if key not in self._warm:
            self.stats.note_compile()
            self._warm.add(key)
        handle = self.engine.dispatch_plan(plan, k_fetch)
        if self.engine.rerank == "exact" and not skip_rerank:
            # immutable: cut to k here; mutable: keep the full fetch window
            # so the collect-time tombstone filter has rows to absorb
            k_out = k_fetch if self.mutable else self.k
            rkey = self._rerank_key(k_fetch, k_out)
            if rkey not in self._warm:
                self.stats.note_compile()
                self._warm.add(rkey)
            handle = self.engine.dispatch_rerank(handle, queries, k_out)
        if self.load_feedback:
            self._load_ewma = (
                self.load_alpha * handle.dev_rows.astype(np.float64)
                + (1.0 - self.load_alpha) * self._load_ewma
            )
        self.stats.note_bucket_hit(plan.pairs_per_dev)
        return handle

    # --------------------- fault tolerance ----------------------------- #

    def live_devices(self) -> np.ndarray:
        """(ndev,) bool live-device mask (a copy)."""
        return self._live.copy()

    def _mark_dead(self, device: int) -> None:
        """Fail a device over: re-route its replicas from the next plan on.

        Idempotent per device.  The mesh keeps its full shape — a dead
        device simply receives only invalid pairs / dummy tiles from
        every later schedule, so no executable shape changes (failover
        never compiles).  Clusters whose only replicas lived there become
        unreachable and degrade with coverage accounting.
        """
        device = int(device)
        if 0 <= device < self._live.shape[0] and self._live[device]:
            self._live[device] = False
            self.stats.note_failover(device)
            self.stats.set_device_health(device, False)
            if self.faults is not None:
                self.faults.note("failover", device=device)

    def _apply_fault_deaths(self, seq: int) -> None:
        """Fold the fault plan's scheduled device deaths into the mask."""
        if self.faults is None:
            return
        for dev in self.faults.dead_devices(seq):
            self._mark_dead(dev)

    def _dispatch_with_retry(
        self, fl: _Flight, plan: SearchPlan
    ) -> SearchPlan:
        """Dispatch with capped-backoff retries, escalating to failover.

        Transient faults (injected via the fault plan's dispatch hook)
        retry up to `retry_limit` times with exponential backoff capped at
        `retry_backoff_max_s`.  Exhausted retries escalate: when the fault
        is attributable to a device, that device fails over, the batch is
        REPLANNED around it on the survivors and the retry budget resets
        (bounded by the device count); unattributable faults propagate.
        Sets `fl.handle` and returns the plan actually dispatched.
        """
        st = self.stats
        attempts = 0
        backoff = self.retry_backoff_s
        escalations = 0
        while True:
            try:
                if self.faults is not None:
                    self.faults.on_dispatch(fl.seq, live=self._live)
                fl.handle = self._dispatch_micro_batch(
                    plan, fl.k_fetch, fl.padded, skip_rerank=fl.skip_rerank
                )
                return plan
            except TransientFault as e:
                if attempts < self.retry_limit:
                    attempts += 1
                    st.note_retry("dispatch")
                    if backoff > 0:
                        time.sleep(min(backoff, self.retry_backoff_max_s))
                    backoff = min(backoff * 2.0, self.retry_backoff_max_s)
                    continue
                if e.device is None or escalations >= self._live.shape[0]:
                    raise
                self._mark_dead(e.device)
                plan = self._plan_micro_batch(fl.padded, nprobe=fl.nprobe_eff)
                fl.steps = self.engine.plan_scan_steps(plan, fl.q_n)
                attempts = 0
                backoff = self.retry_backoff_s
                escalations += 1

    def _await_handle(self, fl: _Flight) -> None:
        """Watchdog for a dispatched batch (the silent-stall fix).

        No-op (collect blocks, exactly the legacy path) unless a collect
        timeout or a fault plan is configured.  Otherwise polls
        `InFlightSearch.is_ready`; an injected hang, or a result still
        not ready at `collect_timeout_s`, raises instead of stalling the
        serving loop forever — `DeviceHang` (attributed) triggers
        failover + refire upstream, an unattributable timeout raises
        `FaultError`.  Injected slow devices are simulated by treating
        the result as not-ready for the configured delay.
        """
        f = self.faults
        delay = 0.0
        if f is not None:
            hang_dev = f.hang_device(fl.seq)
            if hang_dev is not None:
                # the result will never arrive; surface the fault now
                # (with no watchdog configured this is where the loop
                # would have blocked forever)
                raise DeviceHang(
                    f"collect of batch {fl.seq} hung on device {hang_dev}",
                    device=hang_dev,
                )
            delay = f.collect_delay(fl.seq)
        timeout = self.collect_timeout_s
        if timeout is None and delay <= 0.0:
            return
        t0 = fl.t_dispatched if fl.t_dispatched is not None else (
            time.perf_counter()
        )
        while True:
            now = time.perf_counter()
            simulated_busy = now - t0 < delay
            if not simulated_busy and fl.handle.is_ready():
                return
            if timeout is not None and now - t0 > timeout:
                raise FaultError(
                    f"collect of batch {fl.seq} timed out after "
                    f"{timeout:.3f}s (unattributable; no failover target)"
                )
            time.sleep(0.0005)

    def _refire(self, fl: _Flight) -> None:
        """Replan + re-dispatch a flight whose collect hung.

        The padded queries replan under the post-failover live mask at the
        same effective nprobe; the plan-time mutation snapshot (`fl.mut`)
        is reused so the refired batch answers against the corpus state
        its stream position promised.  Queries whose probed clusters all
        kept live replicas come back bit-identical (results are
        placement-invariant); the rest degrade with coverage accounting.
        """
        plan = self._plan_micro_batch(fl.padded, nprobe=fl.nprobe_eff)
        fl.steps = self.engine.plan_scan_steps(plan, fl.q_n)
        self._dispatch_with_retry(fl, plan)
        fl.t_dispatched = time.perf_counter()

    def _collect_flight(self, fl: _Flight) -> tuple[np.ndarray, np.ndarray]:
        """Await + collect one flight, refiring on attributed hangs.

        Bounded: every `DeviceHang` fails one more device over (injected
        hangs are one-shot per batch), so the refire loop runs at most
        ndev times before the mask stops changing.
        """
        while True:
            try:
                self._await_handle(fl)
                break
            except DeviceHang as e:
                self.stats.note_retry("collect")
                self._mark_dead(e.device)
                self._refire(fl)
        return self._collect_micro_batch(
            fl.handle, fl.q_n, fl.t_start, fl.mut, fl.t_dispatched,
            fl.bspan, deadline_late=fl.deadline_late,
            skip_rerank=fl.skip_rerank, steps=fl.steps,
        )

    def health(self) -> dict:
        """Live health summary (the `/healthz` payload; see HEALTH_STATES).

        "overloaded" while the ingress queue is at `queue_limit`
        (admission control is shedding); "degraded" when any device has
        failed over or a deadline forced degraded service; "ok" otherwise.
        """
        ndev = int(self._live.shape[0])
        live = int(self._live.sum())
        depth = self.pending()
        overloaded = (
            self.queue_limit is not None and depth >= self.queue_limit
        )
        degraded = live < ndev or self._deadline_hit
        state = (
            "overloaded" if overloaded
            else "degraded" if degraded
            else "ok"
        )
        return {
            "state": state,
            "queue_depth": depth,
            "queue_limit": self.queue_limit,
            "live_devices": live,
            "n_devices": ndev,
            "dead_devices": [int(d) for d in np.flatnonzero(~self._live)],
            "degraded_queries": self.stats.degraded_queries,
            "rejected_queries": self.stats.rejected_queries,
            "failovers": self.stats.failovers,
            "retries": self.stats.retries,
        }

    # ------------------------------------------------------------------ #

    def _collect_micro_batch(
        self,
        handle: InFlightSearch,
        q_n: int,
        t_start: float,
        mut: tuple | None = None,
        t_dispatched: float | None = None,
        bspan=NULL_SPAN,
        *,
        deadline_late: bool = False,
        skip_rerank: bool = False,
        steps: tuple[int, int, int] = (0, 0, 0),
    ) -> tuple[np.ndarray, np.ndarray]:
        """Block on one in-flight micro-batch; slice padding, record stats.

        `deadline_late` marks the batch as deadline-degraded (counted per
        real query); `skip_rerank` suppresses the cascade counters for a
        batch whose re-rank stage was deadline-skipped.  Coverage
        degradation is read off the plan itself (`lost_q`).  `steps` are
        the batch's scan grid steps (query, pad, bucket) counted at
        dispatch (`MemANNSEngine.plan_scan_steps`).

        `mut` carries the batch's plan-time mutation snapshot
        (delta results + tombstones); the tombstone filter composes with
        the early-pruning top-k merge here, after the device merge.

        `t_dispatched` (when known) splits the pipelined latency honestly:
        collect-start minus dispatch-end is `dispatch_wait` (this batch sat
        behind earlier in-flight work — pipeline queueing, not its own
        cost), and the blocked collect itself is `collect_wait` (residual
        device execution + transfer).  Both land in `upanns_phase_seconds`;
        the end-to-end plan->collect sample is unchanged.  `bspan` is the
        batch's root trace span (`NULL_SPAN` when untraced).
        """
        st = self.stats
        tr = self.tracer
        t0 = time.perf_counter()
        if t_dispatched is not None:
            wait = max(t0 - t_dispatched, 0.0)
            st.dispatch_wait_s += wait
            st.observe_phase("dispatch_wait", wait)
            bspan.add("dispatch_wait", t_dispatched, t0)
        with tr.span("collect", parent=bspan):
            d, i = self.engine.collect(handle)
        t1 = time.perf_counter()
        st.device_s += t1 - t0
        st.m_device.inc(t1 - t0)
        st.collect_wait_s += t1 - t0
        st.observe_phase("collect_wait", t1 - t0)
        st.m_latency.observe(t1 - t_start)
        st.batches += 1
        st.m_batches.inc(scan=handle.plan.scan)
        st.queries += q_n
        st.m_queries.inc(q_n)
        dev_rows = np.asarray(handle.dev_rows)
        st.rows_scanned += int(dev_rows.sum())
        for dev in range(dev_rows.shape[0]):
            if dev_rows[dev]:
                st.m_rows_scanned.inc(float(dev_rows[dev]), device=dev)
        # early-pruning effectiveness: skipped tile bodies vs dispatched
        # tiles, per batch (the bound-tightening profile)
        tiles = self.engine.plan_tile_count(handle.plan)
        skipped = rows = 0
        if handle.prune_stats is not None:
            ps = np.asarray(handle.prune_stats)
            for dev in range(ps.shape[0]):
                if ps[dev, 0]:
                    st.m_tiles_skipped.inc(float(ps[dev, 0]), device=dev)
                if ps[dev, 1]:
                    st.m_rows_pruned.inc(float(ps[dev, 1]), device=dev)
            tot = ps.sum(axis=0)
            skipped, rows = int(tot[0]), int(tot[1])
        st.tiles_dispatched += tiles
        st.m_tiles_dispatched.inc(tiles)
        st.note_scan_steps(steps)
        st.tiles_skipped += skipped
        st.rows_pruned += rows
        frac = skipped / tiles if tiles else 0.0
        st.m_prune_frac.observe(frac)
        if handle.plan.pruned and handle.query_bound is not None:
            # real (unpadded) queries dispatched with a finite warm start
            n_warm = int(np.isfinite(handle.query_bound[:q_n]).sum())
            st.warm_bound_queries += n_warm
            st.m_warm_bound.inc(n_warm)
        if self.engine.rerank == "exact" and not skip_rerank:
            st.reranked_queries += q_n
            st.rerank_candidates += q_n * self._k_fetch()
            st.m_rerank_queries.inc(q_n, rerank="exact")
            st.m_rerank_candidates.inc(q_n * self._k_fetch(), rerank="exact")
        plan = handle.plan
        if plan.lost_q is not None and plan.lost_q.size:
            n_cov = int(plan.degraded_mask()[:q_n].sum())
            if n_cov:
                st.note_degraded(n_cov, "coverage")
        if deadline_late and q_n:
            self._deadline_hit = True
            st.note_degraded(q_n, "deadline")
        if mut is not None:
            dd, di, tomb = mut
            with tr.span("merge", parent=bspan, tombstones=int(tomb.size)):
                d, i = merge_results(d, i, dd, di, tomb, self.k)
            if tomb.size and (i[:q_n] < 0).any():
                # tombstones swallowed a query's whole overfetch window:
                # results are truncated, so compact as soon as the batch
                # drain finishes (tombstone-free serving is exact again)
                self._starved = True
                st.starved_batches += 1
                st.m_starved.inc()
        tr.end_batch(bspan)
        return d[:q_n], i[:q_n]

    def search(self, queries: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Serve a query array of any length via pipelined micro-batches.

        With `pipeline_depth >= 1`, while the device executes micro-batch i
        the host plans micro-batch i+1; the in-flight queue is drained in
        FIFO order, so results come back in the input order regardless of
        depth.  Returns (dists (Q, k), ids (Q, k)); `search_result` serves
        the same stream with per-query degradation accounting attached.
        """
        res = self.search_result(queries)
        return res.dists, res.ids

    def search_result(self, queries: np.ndarray) -> ServingResult:
        """`search` with fault/degradation accounting (see ServingResult).

        The fault-tolerant serving loop: each micro-batch plans around the
        current live-device mask, dispatches with retry + backoff
        (escalating persistent attributable faults to failover), and
        collects under the hang watchdog (attributed hangs fail the device
        over and refire the batch on the survivors).  With a deadline,
        batches planned after the budget elapsed are served degraded
        (reduced nprobe, cascade skipped when immutable) instead of
        compounding the overrun.  No query is ever dropped or crashed:
        every accepted query returns, exactly or flagged degraded.
        """
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        if queries.shape[0] == 0:
            return ServingResult(
                dists=np.zeros((0, self.k), np.float32),
                ids=np.zeros((0, self.k), np.int32),
                degraded=np.zeros(0, bool),
                deadline_degraded=np.zeros(0, bool),
                coverage_lost=np.zeros((0, 2), np.int32),
            )
        depth = max(0, self.pipeline_depth)
        inflight: collections.deque = collections.deque()
        outs_d, outs_i = [], []
        q_total = queries.shape[0]
        degraded = np.zeros(q_total, bool)
        deadline_deg = np.zeros(q_total, bool)
        lost_pairs: list[np.ndarray] = []
        deadline_s = (
            self.deadline_ms / 1e3 if self.deadline_ms is not None else None
        )
        t_admit = time.perf_counter()

        def collect_one():
            fl = inflight.popleft()
            d, i = self._collect_flight(fl)
            outs_d.append(d)
            outs_i.append(i)
            plan = fl.handle.plan
            if plan.lost_q is not None and plan.lost_q.size:
                keep = plan.lost_q < fl.q_n  # padding rows don't count
                if keep.any():
                    lq = plan.lost_q[keep].astype(np.int64) + fl.offset
                    lost_pairs.append(
                        np.stack(
                            [lq, plan.lost_c[keep].astype(np.int64)], axis=1
                        ).astype(np.int32)
                    )
                    degraded[lq] = True
            if fl.deadline_late:
                deadline_deg[fl.offset : fl.offset + fl.q_n] = True
                degraded[fl.offset : fl.offset + fl.q_n] = True

        mutating = self.engine.mutation_active
        k_fetch_full = self._k_fetch()
        st = self.stats
        tr = self.tracer
        for s in range(0, q_total, self.micro_batch):
            chunk = queries[s : s + self.micro_batch]
            seq = self._batch_seq
            self._batch_seq += 1
            self._apply_fault_deaths(seq)
            late = (
                deadline_s is not None
                and time.perf_counter() - t_admit > deadline_s
            )
            # deadline degradation: shrink nprobe; an immutable cascade
            # additionally skips the re-rank stage (plain ADC top-k at k).
            # Mutable engines keep their fetch/delta shapes (those are the
            # warmed ones) and only shrink nprobe.
            skip_rerank = (
                late and self.engine.rerank == "exact" and not self.mutable
            )
            nprobe_eff = self.degrade_nprobe if late else self.nprobe
            k_fetch = self.k if skip_rerank else k_fetch_full
            bspan = tr.begin_batch(
                queries=int(chunk.shape[0]), scan=self.engine.scan
            )
            t0 = time.perf_counter()
            padded = self._pad_chunk(chunk)
            with tr.span("plan", parent=bspan, nprobe=nprobe_eff):
                plan = self._plan_micro_batch(padded, nprobe=nprobe_eff)
            t1a = time.perf_counter()
            mut = None
            if mutating:
                # delta search + tombstone snapshot at plan time: host work,
                # overlappable with in-flight device batches like planning
                with tr.span("delta", parent=bspan):
                    mut = self._delta_micro_batch(padded, plan, k_fetch)
            t1 = time.perf_counter()
            # host planning is hidden behind in-flight device work
            st.note_host(t1 - t0, overlapped=bool(inflight))
            st.observe_phase("plan", t1a - t0)
            if mutating:
                st.observe_phase("delta", t1 - t1a)
            steps = self.engine.plan_scan_steps(plan, chunk.shape[0])
            fl = _Flight(
                handle=None, q_n=chunk.shape[0], offset=s, t_start=t0,
                mut=mut, t_dispatched=None, bspan=bspan, seq=seq,
                padded=padded, nprobe_eff=nprobe_eff, k_fetch=k_fetch,
                skip_rerank=skip_rerank, deadline_late=late, steps=steps,
            )
            with tr.span(
                "dispatch", parent=bspan, pairs_per_dev=plan.pairs_per_dev,
                tiles_per_dev=plan.tiles_per_dev, steps_query=steps[0],
                steps_pad=steps[1], steps_bucket=steps[2],
            ):
                self._dispatch_with_retry(fl, plan)
            t2 = time.perf_counter()
            st.device_s += t2 - t1
            st.m_device.inc(t2 - t1)
            st.observe_phase("dispatch", t2 - t1)
            fl.t_dispatched = t2
            inflight.append(fl)
            while len(inflight) > depth:
                collect_one()
        while inflight:
            collect_one()
        if self._starved:  # after the drain: no batches in flight
            self._starved = False
            self.compact()
        return ServingResult(
            dists=np.concatenate(outs_d),
            ids=np.concatenate(outs_i),
            degraded=degraded,
            deadline_degraded=deadline_deg,
            coverage_lost=(
                np.concatenate(lost_pairs)
                if lost_pairs
                else np.zeros((0, 2), np.int32)
            ),
        )

    # ------------------------------------------------------------------ #

    def submit(self, queries: np.ndarray) -> int:
        """Enqueue queries for the next `flush()` (request accumulation).

        Admission control: with `queue_limit` set, the ingress queue is
        bounded — queries beyond the remaining room are REJECTED (shed,
        not stalled), counted in `upanns_rejected_queries_total`, and
        `health()` reports "overloaded" while the queue is full.  Returns
        the number of queries actually admitted (== all of them when no
        limit is configured; legacy callers may ignore it).
        """
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        n = int(queries.shape[0])
        if n == 0:
            return 0
        if self.queue_limit is not None:
            room = self.queue_limit - self.pending()
            if room <= 0:
                self.stats.note_rejected(n)
                return 0
            if n > room:
                self.stats.note_rejected(n - room)
                queries = queries[:room]
                n = room
        self._pending.append(queries)
        self.stats.set_queue_depth(self.pending())
        return n

    def pending(self) -> int:
        return sum(q.shape[0] for q in self._pending)

    def flush(self) -> tuple[np.ndarray, np.ndarray]:
        """Serve everything submitted since the last flush, in order."""
        if not self._pending:
            return (
                np.zeros((0, self.k), np.float32),
                np.zeros((0, self.k), np.int32),
            )
        queries = np.concatenate(self._pending)
        self._pending = []
        self.stats.set_queue_depth(0)
        return self.search(queries)

    def flush_result(self) -> ServingResult:
        """`flush` with degradation accounting (see `search_result`)."""
        if not self._pending:
            return self.search_result(np.zeros((0, 1), np.float32))
        queries = np.concatenate(self._pending)
        self._pending = []
        self.stats.set_queue_depth(0)
        return self.search_result(queries)

    # ----------------------- online mutation -------------------------- #

    def _require_mutable(self) -> None:
        if not self.mutable:
            raise RuntimeError(
                "this ServingEngine was built with mutable=False; "
                "construct with mutable=True to serve inserts/deletes"
            )

    def _mutation_gauges(self) -> None:
        d = self.engine.delta
        self.stats.set_mutation_gauges(
            d.occupancy if d is not None else 0.0,
            d.tombstone_count if d is not None else 0,
        )

    def insert(self, ids: np.ndarray, vectors: np.ndarray) -> int:
        """Insert vectors into the live index; next search sees them.

        Auto-compacts when the delta buffer crosses `compact_occupancy`.
        """
        self._require_mutable()
        n = insert_into(self.engine, ids, vectors)
        self.stats.note_inserts(n)
        self._maybe_compact()
        self._mutation_gauges()
        return n

    def delete(self, ids: np.ndarray) -> int:
        """Tombstone ids; auto-compacts at `tombstone_limit`."""
        self._require_mutable()
        n = delete_from(self.engine, ids)
        self.stats.note_deletes(n)
        self._maybe_compact()
        self._mutation_gauges()
        return n

    def _maybe_compact(self) -> None:
        d = self.engine.delta
        if d is None:
            return
        if (
            d.occupancy >= self.compact_occupancy
            or d.tombstone_count >= self.tombstone_limit
        ):
            self.compact()

    def compact(self):
        """Merge the delta into the main index (incremental re-placement +
        shard delta-rebuild); returns the CompactionReport."""
        self._require_mutable()
        # compactions run between batches, so the span roots its own tree
        with self.tracer.span("compaction"):
            report = compact_engine(
                self.engine, replace_threshold=self.replace_threshold
            )
        if report.latency_s > 0.0:
            self.stats.note_compaction(report.latency_s)
        self._mutation_gauges()
        return report
