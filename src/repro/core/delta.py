"""DeltaIndex: the host-side mutation buffer of the online mutation subsystem.

The main `IVFPQIndex` is immutable (cluster-sorted CSR storage packed into
device shards); real serving traffic mutates the corpus continuously.  The
delta layer makes that possible without touching the frozen main index:

  * **inserts** are PQ-encoded immediately (same jitted assignment/encoding
    path as `build_index`, so a later compaction is bit-identical to a
    from-scratch re-encode) and appended to a fixed-capacity buffer whose
    capacity grows in power-of-two buckets -- the delta search is jitted on
    (Q, capacity) shapes, so steady-state serving never recompiles while the
    buffer fills;
  * **deletes** become tombstones: a global id set filtered out of main-index
    results at collect time, plus a dead-row mask for ids still in the delta;
  * **search** scans the buffer with the same ADC contract as the device
    kernels (per-(query, probed-centroid) LUT, residual codes), merged into
    the main top-k by the serving layer;
  * **compaction** (`compact_index`) merges live delta rows into the CSR
    storage and drops tombstoned rows, preserving the invariant documented on
    `IVFPQIndex`: within a cluster, surviving original rows keep their order
    and delta rows follow in insertion order -- exactly the order
    `encode_index` produces over (survivors, then inserts), which is what
    makes post-compaction search results bit-identical to a from-scratch
    rebuild with the same trained centroids/codebooks.

Everything here is index-level (numpy + small jitted blocks); placement and
shard updates live in `repro.retrieval.mutation`.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import (
    IVFPQIndex,
    assign_clusters,
    encode_vectors,
)
from repro.core.lut import build_lut
from repro.core.search import masked_topk_smallest

# smallest delta capacity bucket; also the floor for the padded insert-batch
# encode shapes, so tiny interactive inserts reuse one compiled encoder
DELTA_FLOOR = 64


def _pow2(n: int, floor: int = DELTA_FLOOR) -> int:
    return max(floor, 1 << math.ceil(math.log2(max(n, 1))))


@dataclasses.dataclass
class DeltaIndex:
    """Append buffer of PQ-encoded inserts + tombstone set for deletes.

    Rows [0, n) are occupied, in insertion order; arrays are padded to
    `capacity` (a power of two) so the jitted delta search compiles once per
    (batch, capacity) bucket.  `dead[i]` marks a delta row whose id was
    deleted again before compaction; `tombstones` is the global id set
    (main-index ids and dead delta ids both appear there, which keeps the
    collect-time filter a single membership test).

    Attributes:
      codes: (capacity, M) uint8 PQ codes (residual vs assigned centroid).
      assign: (capacity,) int32 nearest coarse centroid per row.
      vec_ids: (capacity,) int32 global ids, -1 on unused rows.
      dead: (capacity,) bool, True where the row was tombstoned.
      n: occupied row count.
      tombstones: set of deleted global ids (cleared by compaction).
      vectors: (capacity, D) f32 ORIGINAL-space raw vectors of the buffered
        inserts, allocated lazily on first insert.  Feeds the exact re-rank
        cascade (delta candidates re-rank through the same kernel as main
        candidates) and the raw-store update at compaction.  Always in the
        original space even under an OPQ rotation — only codes/assign live
        in the rotated space.
    """

    codes: np.ndarray
    assign: np.ndarray
    vec_ids: np.ndarray
    dead: np.ndarray
    n: int = 0
    tombstones: set[int] = dataclasses.field(default_factory=set)
    vectors: np.ndarray | None = None

    @classmethod
    def create(cls, m: int, capacity: int = 4096) -> "DeltaIndex":
        cap = _pow2(capacity)
        return cls(
            codes=np.zeros((cap, m), np.uint8),
            assign=np.zeros(cap, np.int32),
            vec_ids=np.full(cap, -1, np.int32),
            dead=np.zeros(cap, bool),
        )

    @property
    def capacity(self) -> int:
        return self.codes.shape[0]

    @property
    def occupancy(self) -> float:
        return self.n / self.capacity

    def live_mask(self) -> np.ndarray:
        """(capacity,) bool: occupied and not tombstoned."""
        mask = np.zeros(self.capacity, bool)
        mask[: self.n] = ~self.dead[: self.n]
        return mask

    @property
    def live_count(self) -> int:
        return int(self.n - self.dead[: self.n].sum())

    @property
    def tombstone_count(self) -> int:
        return len(self.tombstones)

    def tombstone_array(self) -> np.ndarray:
        """Sorted int64 view of the tombstone set (for vectorized isin)."""
        if not self.tombstones:
            return np.zeros(0, np.int64)
        return np.fromiter(
            sorted(self.tombstones), np.int64, count=len(self.tombstones)
        )

    @property
    def active(self) -> bool:
        """True when searches must consult the delta layer at all."""
        return self.live_count > 0 or bool(self.tombstones)

    # ------------------------------------------------------------------ #

    def _grow(self, need: int) -> None:
        cap = _pow2(need, floor=self.capacity)
        if cap == self.capacity:
            return
        pad = cap - self.capacity
        self.codes = np.concatenate(
            [self.codes, np.zeros((pad, self.codes.shape[1]), np.uint8)]
        )
        self.assign = np.concatenate([self.assign, np.zeros(pad, np.int32)])
        self.vec_ids = np.concatenate(
            [self.vec_ids, np.full(pad, -1, np.int32)]
        )
        self.dead = np.concatenate([self.dead, np.zeros(pad, bool)])
        if self.vectors is not None:
            self.vectors = np.concatenate(
                [
                    self.vectors,
                    np.zeros((pad, self.vectors.shape[1]), np.float32),
                ]
            )

    def insert(
        self,
        centroids: np.ndarray,
        codebook: np.ndarray,
        ids: np.ndarray,
        vectors: np.ndarray,
        rotation: np.ndarray | None = None,
    ) -> int:
        """Encode + append a batch of new vectors; returns rows appended.

        Ids must be fresh (never currently live in main or delta, and not
        tombstoned -- re-using a deleted id would make the tombstone filter
        eat the new row).  The encode runs on inputs padded to a power-of-two
        batch bucket, so interactive insert streams hit a handful of
        compiled shapes instead of one per batch size.

        `vectors` are ORIGINAL-space; with an OPQ `rotation` they are
        rotated before assignment/encoding (centroids/codebooks live in the
        rotated space) while the raw copy kept for the re-rank cascade
        stays unrotated.
        """
        ids = np.atleast_1d(np.asarray(ids, np.int32))
        vectors = np.asarray(vectors, np.float32)
        if vectors.ndim == 1:
            vectors = vectors[None]
        b = ids.shape[0]
        if b == 0:
            return 0
        if vectors.shape[0] != b:
            raise ValueError(f"{b} ids vs {vectors.shape[0]} vectors")
        clash = self.tombstones.intersection(ids.tolist())
        if clash:
            raise ValueError(
                f"ids {sorted(clash)[:8]} were deleted earlier; re-inserting "
                "a tombstoned id is unsupported until after a compaction"
            )
        if self.vectors is None:
            self.vectors = np.zeros(
                (self.capacity, vectors.shape[1]), np.float32
            )
        self._grow(self.n + b)
        # pad the encode batch to a pow2 bucket (stable jit shapes), slice off
        bpad = _pow2(b)
        vpad = np.concatenate(
            [vectors, np.broadcast_to(vectors[:1], (bpad - b, vectors.shape[1]))]
        )
        if rotation is not None:
            vpad = vpad @ rotation
        assign_pad = assign_clusters(centroids, vpad)
        codes = encode_vectors(codebook, centroids, vpad, assign_pad)[:b]
        assign = assign_pad[:b]
        s = self.n
        self.codes[s : s + b] = codes
        self.assign[s : s + b] = assign
        self.vec_ids[s : s + b] = ids
        self.dead[s : s + b] = False
        self.vectors[s : s + b] = vectors
        self.n += b
        return b

    def delete(self, ids: np.ndarray) -> int:
        """Tombstone a batch of global ids; returns newly tombstoned count.

        Ids living in the delta are additionally marked dead so the delta
        search prunes them without a set lookup; unknown ids are recorded
        too (they may name main-index rows -- membership is not checked
        here, compaction simply drops nothing for ids that never existed).
        """
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        new = 0
        for i in ids.tolist():
            if int(i) not in self.tombstones:
                self.tombstones.add(int(i))
                new += 1
        if self.n:
            self.dead[: self.n] |= np.isin(self.vec_ids[: self.n], ids)
        return new

    def reset(self) -> None:
        """Empty the buffer + tombstones, keeping capacity (post-compaction)."""
        self.n = 0
        self.dead[:] = False
        self.vec_ids[:] = -1
        self.tombstones = set()


# ---------------------------------------------------------------------- #
# delta search: same ADC contract as the device kernels, jitted on
# (Q, capacity) shapes so churn never recompiles steady-state serving
# ---------------------------------------------------------------------- #


@functools.partial(jax.jit, static_argnames=("nprobe", "k"))
def delta_topk_block(
    centroids,   # (C, D) f32
    codebook,    # (M, 256, dsub) f32
    queries,     # (Q, D) f32
    codes,       # (cap, M) uint8
    assign,      # (cap,) int32
    vec_ids,     # (cap,) int32
    alive,       # (cap,) bool
    bound,       # (Q,) f32 per-query upper bound on reportable distances
    *,
    nprobe: int,
    k: int,
):
    """Top-k of the delta buffer under the main index's probe semantics.

    A delta row competes for query q iff its assigned centroid is among q's
    nprobe probed clusters (exactly the visibility rule of the main path),
    and its distance is the ADC sum over the (query, that centroid) LUT --
    the same value the device scan would produce for the same codes.  All
    shapes are static: Q x capacity, with capacity a power-of-two bucket.

    `bound` applies the device kernels' early-pruning semantics to the
    delta layer: rows with distance strictly above `bound[q]` are masked
    out exactly like pruned kernel lanes ((+inf, -1)).  Callers must pass
    a value no smaller than the largest distance that can still reach the
    merged output (serving derives it from the warm-start bound machinery,
    with tombstone slack); +inf disables the filter.

    Returns (dists (Q, k) f32 with +inf padding, ids (Q, k) int32 with -1).
    """
    from repro.core.index import filter_clusters  # local: avoid import cycle

    probed, qmc = filter_clusters(centroids, queries, nprobe)
    m = codebook.shape[0]
    q_n = queries.shape[0]
    a = m * 256
    luts = jax.vmap(
        lambda rows: jax.vmap(lambda r: build_lut(codebook, r))(rows)
    )(qmc)                                             # (Q, nprobe, M, 256)
    luts_flat = luts.reshape(q_n, nprobe * a)
    addr = (
        jnp.arange(m, dtype=jnp.int32)[None, :] * 256
        + codes.astype(jnp.int32)
    )                                                  # (cap, M)
    match = probed[:, :, None] == assign[None, None, :]  # (Q, nprobe, cap)
    found = jnp.any(match, axis=1) & alive[None, :]      # (Q, cap)
    col = jnp.argmax(match, axis=1).astype(jnp.int32)    # (Q, cap)

    def per_q(lut_flat, colq):
        idx = colq[:, None] * a + addr                  # (cap, M) gather
        return jnp.take(lut_flat, idx, axis=0).sum(axis=-1)

    dists = jax.vmap(per_q)(luts_flat, col)             # (Q, cap)
    found = found & (dists <= bound[:, None])
    # top_k needs k <= cap: a buffer smaller than k pads with (+inf, -1)
    kk = min(k, dists.shape[1])
    vals, idx = masked_topk_smallest(dists, found, kk)
    good = vals < jnp.finfo(vals.dtype).max
    out_i = jnp.where(good, vec_ids[idx], -1)
    out_d = jnp.where(good, vals, jnp.inf)
    pad = ((0, 0), (0, k - kk))
    return (
        jnp.pad(out_d, pad, constant_values=jnp.inf),
        jnp.pad(out_i, pad, constant_values=-1),
    )


def delta_topk(
    delta: DeltaIndex,
    centroids: np.ndarray,
    codebook: np.ndarray,
    queries: np.ndarray,
    nprobe: int,
    k: int,
    bound: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Host wrapper around `delta_topk_block` (numpy in / numpy out).

    `bound` is the optional (Q,) early-pruning distance cutoff (see
    `delta_topk_block`); None scans unbounded.  The bound array is always
    materialized so both modes share one jitted executable.
    """
    if k > delta.capacity:
        raise ValueError(
            f"k={k} > delta capacity {delta.capacity}; create the delta "
            f"with capacity >= k"
        )
    q_n = np.asarray(queries).shape[0]
    if bound is None:
        bound = np.full(q_n, np.inf, np.float32)
    d, i = delta_topk_block(
        jnp.asarray(centroids, jnp.float32),
        jnp.asarray(codebook, jnp.float32),
        jnp.asarray(queries, jnp.float32),
        jnp.asarray(delta.codes),
        jnp.asarray(delta.assign),
        jnp.asarray(delta.vec_ids),
        jnp.asarray(delta.live_mask()),
        jnp.asarray(bound, jnp.float32),
        nprobe=nprobe,
        k=k,
    )
    return np.asarray(d), np.asarray(i)


def merge_results(
    main_d: np.ndarray,
    main_i: np.ndarray,
    delta_d: np.ndarray | None,
    delta_i: np.ndarray | None,
    tombstones: np.ndarray,
    k: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Compose tombstone filtering with the top-k merge (host side).

    Tombstoned main-path hits are masked to (+inf, -1) -- the same encoding
    the kernels use for pruned lanes, so the merge's stable sort composes
    with the early-pruning top-k exactly: surviving candidates keep their
    ADC order, main-path rows win ties against delta rows (matching the
    post-compaction layout, where old rows precede inserted rows within a
    cluster).

    Args:
      main_d / main_i: (Q, k_fetch) main-path results (k_fetch >= k when
        tombstones are present -- the overfetch absorbs filtered rows).
      delta_d / delta_i: (Q, kd) delta results, already tombstone-free
        (None when the buffer is empty).
      tombstones: sorted id array from `DeltaIndex.tombstone_array()`.

    Returns (dists (Q, k), ids (Q, k)).
    """
    if tombstones.size:
        hit = np.isin(main_i, tombstones)
        main_d = np.where(hit, np.inf, main_d)
        main_i = np.where(hit, -1, main_i)
    if delta_d is not None:
        main_d = np.concatenate([main_d, delta_d], axis=1)
        main_i = np.concatenate([main_i, delta_i.astype(main_i.dtype)], axis=1)
    if main_d.shape[1] == k and tombstones.size == 0 and delta_d is None:
        return main_d, main_i  # already sorted ascending by the device merge
    sel = np.argsort(main_d, axis=1, kind="stable")[:, :k]
    return (
        np.take_along_axis(main_d, sel, axis=1),
        np.take_along_axis(main_i, sel, axis=1),
    )


# ---------------------------------------------------------------------- #
# compaction (index level)
# ---------------------------------------------------------------------- #


@dataclasses.dataclass
class CompactionDelta:
    """What a compaction changed, per cluster (consumed by re-placement)."""

    old_sizes: np.ndarray      # (C,) rows per cluster before
    new_sizes: np.ndarray      # (C,) rows per cluster after
    content_changed: np.ndarray  # (C,) bool: any row added or removed
    merged: int                # live delta rows merged in
    dropped: int               # tombstoned rows removed (main + delta)


def compact_index(
    index: IVFPQIndex, delta: DeltaIndex
) -> tuple[IVFPQIndex, CompactionDelta]:
    """Merge the delta buffer into the CSR index, dropping tombstoned rows.

    Within each cluster the output keeps surviving original rows in their
    stored order, then appends live delta rows in insertion order -- the
    exact row order `encode_index` produces for (survivors, then inserts),
    so a search over the compacted index is bit-identical to a from-scratch
    re-encode of the surviving vectors with the same trained
    centroids/codebooks.  Does NOT mutate its inputs; the caller resets the
    delta after re-placing/re-packing shards.
    """
    tomb = delta.tombstone_array()
    old_sizes = index.cluster_sizes().astype(np.int64)
    row_cluster = np.repeat(
        np.arange(index.n_clusters, dtype=np.int32), old_sizes
    )
    keep = (
        ~np.isin(index.vec_ids, tomb)
        if tomb.size
        else np.ones(index.n_vectors, bool)
    )
    live = delta.live_mask()[: delta.n]

    all_codes = np.concatenate(
        [index.codes[keep], delta.codes[: delta.n][live]]
    )
    all_assign = np.concatenate(
        [row_cluster[keep], delta.assign[: delta.n][live]]
    )
    all_ids = np.concatenate(
        [index.vec_ids[keep], delta.vec_ids[: delta.n][live]]
    )
    # stable sort: main rows (already cluster-sorted, original order) come
    # first within each cluster, delta rows follow in insertion order
    order = np.argsort(all_assign, kind="stable")
    new_sizes = np.bincount(all_assign, minlength=index.n_clusters).astype(
        np.int64
    )
    offsets = np.zeros(index.n_clusters + 1, np.int64)
    np.cumsum(new_sizes, out=offsets[1:])
    new_index = IVFPQIndex(
        centroids=index.centroids,
        codebook=index.codebook,
        codes=all_codes[order],
        vec_ids=all_ids[order],
        offsets=offsets,
        rotation=index.rotation,
    ).validate()

    removed = np.zeros(index.n_clusters, np.int64)
    if tomb.size:
        np.add.at(removed, row_cluster[~keep], 1)
    added = np.bincount(
        delta.assign[: delta.n][live], minlength=index.n_clusters
    ).astype(np.int64)
    content_changed = (removed > 0) | (added > 0)
    return new_index, CompactionDelta(
        old_sizes=old_sizes,
        new_sizes=new_sizes,
        content_changed=content_changed,
        merged=int(live.sum()),
        dropped=int((~keep).sum() + (delta.n - live.sum())),
    )
