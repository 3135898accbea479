"""IVFPQ index assembly (offline phase) and flat single-host search.

Mirrors the paper's offline phase: IVF coarse clustering -> residuals -> PQ
encoding -> cluster-sorted code storage (CSR layout).  The flat `search` here
is the "Faiss-CPU"-style baseline used by tests and benchmarks; the
distributed MemANNS path lives in repro/retrieval/.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kmeans import kmeans, _pairwise_sq_l2
from repro.core.lut import build_lut
from repro.core.pq import pq_encode, train_opq, train_pq
from repro.core.search import adc_scan, masked_topk_smallest


@dataclasses.dataclass
class IVFPQIndex:
    """Cluster-sorted IVFPQ index.

    Storage invariant (CSR): `codes`/`vec_ids` hold the rows of cluster c
    contiguously at `[offsets[c], offsets[c + 1])`, clusters in ascending id
    order, and within a cluster rows keep their original insertion order.
    `cluster_codes`/`cluster_ids` slice directly on this invariant, and the
    shard packer copies those slices verbatim — a delta merge that violated
    it would silently hand every downstream layer the wrong rows, so
    `validate()` asserts it and mutation paths call it after every
    compaction.

    Attributes:
      centroids: (C, D) coarse centroids.  With an OPQ rotation these (and
        the codes) live in the ROTATED space.
      codebook: (M, 256, d_sub) PQ codebooks (of residuals).
      codes: (N, M) uint8, rows sorted by cluster id.
      vec_ids: (N,) int32 global vector ids, same order as codes (for a
        freshly built index these are positions into the build input; the
        mutation layer appends new ids past that range).
      offsets: (C + 1,) int64 CSR offsets into codes/vec_ids.
      rotation: optional (D, D) orthonormal OPQ rotation (see
        `core.pq.train_opq`).  When set, queries must be rotated with
        `rotate()` before comparing against centroids or building LUTs;
        anything in the original space (raw vectors, exact re-rank,
        brute-force ground truth) stays unrotated — L2 is R-invariant.
    """

    centroids: np.ndarray
    codebook: np.ndarray
    codes: np.ndarray
    vec_ids: np.ndarray
    offsets: np.ndarray
    rotation: np.ndarray | None = None

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_vectors(self) -> int:
        return self.codes.shape[0]

    @property
    def m(self) -> int:
        return self.codes.shape[1]

    def cluster_sizes(self) -> np.ndarray:
        return np.diff(self.offsets)

    def rotate(self, vectors: np.ndarray) -> np.ndarray:
        """Map original-space vectors into this index's coding space.

        Identity when no OPQ rotation was trained; otherwise `v @ R`.
        Every query entry point (flat search, engine scheduling, delta
        scans) routes through this before touching centroids or codes.
        """
        if self.rotation is None:
            return vectors
        return np.asarray(vectors, np.float32) @ self.rotation

    def cluster_codes(self, c: int) -> np.ndarray:
        return self.codes[self.offsets[c] : self.offsets[c + 1]]

    def cluster_ids(self, c: int) -> np.ndarray:
        return self.vec_ids[self.offsets[c] : self.offsets[c + 1]]

    def validate(self) -> "IVFPQIndex":
        """Assert the contiguous CSR storage invariant; returns self.

        Checks: offsets are monotone and span exactly the stored rows,
        codes/vec_ids agree on the row count, and no vector id appears
        twice (a corrupted delta merge would typically duplicate or drop
        rows, which this catches in O(N log N)).
        """
        if self.offsets.shape != (self.n_clusters + 1,):
            raise ValueError(
                f"offsets shape {self.offsets.shape} != (C+1,)="
                f"({self.n_clusters + 1},)"
            )
        if self.offsets[0] != 0 or (np.diff(self.offsets) < 0).any():
            raise ValueError("offsets must start at 0 and be non-decreasing")
        if int(self.offsets[-1]) != self.codes.shape[0]:
            raise ValueError(
                f"offsets[-1]={int(self.offsets[-1])} != "
                f"codes rows {self.codes.shape[0]}"
            )
        if self.vec_ids.shape[0] != self.codes.shape[0]:
            raise ValueError(
                f"vec_ids rows {self.vec_ids.shape[0]} != "
                f"codes rows {self.codes.shape[0]}"
            )
        if np.unique(self.vec_ids).size != self.vec_ids.size:
            raise ValueError("duplicate vector ids in index")
        return self


_assign_fn = jax.jit(
    lambda x, c: jnp.argmin(_pairwise_sq_l2(x, c), axis=1).astype(jnp.int32)
)
_encode_fn = jax.jit(pq_encode)


def assign_clusters(centroids: np.ndarray, xs: np.ndarray) -> np.ndarray:
    """(N,) int32 nearest coarse centroid per vector, chunked (billion-scale
    friendly).  The single shared jitted argmin keeps insert-time assignment
    bit-identical to build-time assignment."""
    xs = np.asarray(xs, np.float32)
    n = xs.shape[0]
    assign = np.empty(n, np.int32)
    chunk = max(1, min(n, 1 << 18))
    cent = jnp.asarray(centroids)
    for s in range(0, n, chunk):
        assign[s : s + chunk] = np.asarray(
            _assign_fn(jnp.asarray(xs[s : s + chunk]), cent)
        )
    return assign


def encode_vectors(
    codebook: np.ndarray,
    centroids: np.ndarray,
    xs: np.ndarray,
    assign: np.ndarray,
) -> np.ndarray:
    """(N, M) uint8 PQ codes of the residuals xs - centroids[assign]."""
    xs = np.asarray(xs, np.float32)
    n = xs.shape[0]
    m = codebook.shape[0]
    residuals = xs - centroids[assign]
    codes = np.empty((n, m), np.uint8)
    chunk = max(1, min(n, 1 << 18))
    cb = jnp.asarray(codebook)
    for s in range(0, n, chunk):
        codes[s : s + chunk] = np.asarray(
            _encode_fn(cb, jnp.asarray(residuals[s : s + chunk]))
        )
    return codes


def encode_index(
    centroids: np.ndarray,
    codebook: np.ndarray,
    xs: np.ndarray,
    vec_ids: np.ndarray | None = None,
    assign: np.ndarray | None = None,
    rotation: np.ndarray | None = None,
) -> IVFPQIndex:
    """Assemble an IVFPQIndex from *already trained* centroids + codebooks.

    This is the deterministic second half of `build_index` (assignment,
    residual encoding, CSR packing) without re-running k-means / PQ
    training.  The mutation layer's compaction is defined against it: a
    compacted index must be bit-identical to `encode_index` over the
    surviving vectors in (original, then inserted) order.

    Args:
      vec_ids: optional (N,) global ids of xs rows; defaults to 0..N-1.
      assign: optional precomputed (N,) cluster assignment (must equal
        `assign_clusters(centroids, xs)`; `build_index` passes the one it
        already computed so the full dataset is assigned exactly once).
      rotation: optional OPQ rotation to RECORD on the index.  `centroids`
        and `xs` must already be rotated — this function never applies it
        (keeping the compaction bit-identity contract rotation-agnostic).
    """
    centroids = np.asarray(centroids, np.float32)
    codebook = np.asarray(codebook, np.float32)
    n = np.asarray(xs).shape[0]
    n_clusters = centroids.shape[0]
    if assign is None:
        assign = assign_clusters(centroids, xs)
    codes = encode_vectors(codebook, centroids, xs, assign)
    if vec_ids is None:
        vec_ids = np.arange(n, dtype=np.int32)
    order = np.argsort(assign, kind="stable")
    sizes = np.bincount(assign, minlength=n_clusters)
    offsets = np.zeros(n_clusters + 1, np.int64)
    np.cumsum(sizes, out=offsets[1:])
    return IVFPQIndex(
        centroids=centroids,
        codebook=codebook,
        codes=codes[order],
        vec_ids=np.asarray(vec_ids, np.int32)[order],
        offsets=offsets,
        rotation=rotation,
    ).validate()


def build_index(
    key: jax.Array,
    xs: np.ndarray,
    n_clusters: int,
    m: int,
    kmeans_iters: int = 25,
    pq_iters: int = 20,
    train_subsample: int | None = None,
    opq_iters: int = 0,
) -> IVFPQIndex:
    """Offline phase: IVF + PQ.  Host-side (numpy) bookkeeping, JAX compute.

    Args:
      n_clusters: coarse IVF cluster count C.
      m: PQ subspace count (D % m == 0).
      train_subsample: optional row cap for k-means/PQ training (the full
        dataset is still assigned + encoded).
      opq_iters: > 0 trains an OPQ-style whole-space rotation on the
        training residuals (`core.pq.train_opq`) before PQ; centroids and
        codes are then stored in the rotated space and the rotation is
        recorded on the index for query-time use (`IVFPQIndex.rotate`).
    """
    xs = np.asarray(xs, np.float32)
    n = xs.shape[0]
    k_ivf, k_pq = jax.random.split(key)

    train = xs
    if train_subsample is not None and train_subsample < n:
        sel = np.random.default_rng(0).choice(n, train_subsample, replace=False)
        train = xs[sel]

    centroids, _ = kmeans(k_ivf, jnp.asarray(train), n_clusters, iters=kmeans_iters)
    centroids = np.asarray(centroids)

    # assign the full dataset once; PQ trains on the (subsampled) residuals
    assign = assign_clusters(centroids, xs)
    if train_subsample is not None and train_subsample < n:
        res_train = train - centroids[assign[sel]]
    else:
        res_train = xs - centroids[assign]
    if opq_iters > 0:
        # whole-space rotation: (x - c)R == xR - cR, so rotating centroids
        # and data once rotates every residual; the original-space cluster
        # assignment carries over (R preserves distances)
        rotation, codebook = train_opq(
            k_pq, res_train, m, pq_iters=pq_iters, opq_iters=opq_iters
        )
        return encode_index(
            centroids @ rotation, codebook, xs @ rotation,
            assign=assign, rotation=rotation,
        )
    codebook = np.asarray(train_pq(k_pq, jnp.asarray(res_train), m, iters=pq_iters))

    return encode_index(centroids, codebook, xs, assign=assign)


@functools.partial(jax.jit, static_argnames=("nprobe",))
def filter_clusters(
    centroids: jax.Array, queries: jax.Array, nprobe: int
) -> tuple[jax.Array, jax.Array]:
    """Online stage (a): pick the nprobe closest coarse centroids per query.

    Returns (cluster_ids (Q, nprobe), q_minus_c (Q, nprobe, D)).
    Runs on the host CPU in the paper; here it is a tiny jitted GEMM.
    """
    d2 = _pairwise_sq_l2(queries, centroids)           # (Q, C)
    _, cids = jax.lax.top_k(-d2, nprobe)               # (Q, nprobe)
    qmc = queries[:, None, :] - centroids[cids]        # (Q, nprobe, D)
    return cids, qmc


def search(
    index: IVFPQIndex,
    queries: np.ndarray,
    nprobe: int,
    k: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Flat (single-device) IVFPQ search -- the CPU-Faiss-style baseline.

    Returns (dists (Q, k), ids (Q, k)) of approximate nearest neighbours.
    ADC (quantized) distances; queries are rotated on entry when the index
    carries an OPQ rotation.
    """
    queries = jnp.asarray(index.rotate(np.asarray(queries, np.float32)))
    cids, qmc = filter_clusters(jnp.asarray(index.centroids), queries, nprobe)
    cids_np = np.asarray(cids)
    codebook = jnp.asarray(index.codebook)

    q_n = queries.shape[0]
    out_d = np.full((q_n, k), np.inf, np.float32)
    out_i = np.full((q_n, k), -1, np.int64)

    scan_fn = jax.jit(
        lambda lut, codes, valid: masked_topk_smallest(
            adc_scan(lut, codes), valid, k
        )
    )
    lut_fn = jax.jit(build_lut)

    sizes = index.cluster_sizes()
    for qi in range(q_n):
        # concatenate this query's probed clusters (host gather), one scan
        probe = cids_np[qi]
        segs = [index.cluster_codes(c) for c in probe]
        ids = np.concatenate([index.cluster_ids(c) for c in probe])
        lens = np.asarray([len(s) for s in segs])
        total = int(lens.sum())
        if total == 0:
            continue
        codes = np.concatenate(segs, axis=0)
        # per-point LUT row: which probe segment each point belongs to
        seg_of = np.repeat(np.arange(nprobe), lens)
        luts = np.asarray(jax.vmap(lambda r: lut_fn(codebook, r))(qmc[qi]))
        # scan each probe segment with its own LUT, merge
        best_d = np.full(k, np.inf, np.float32)
        best_i = np.full(k, -1, np.int64)
        for pi in range(nprobe):
            seg = segs[pi]
            n_seg = len(seg)
            if n_seg == 0:
                continue
            kk = min(k, n_seg)
            # pad to a pow2 width >= k: top_k needs k rows, and a few
            # widths keep the jitted scan from compiling per cluster size
            width = max(k, 1 << (n_seg - 1).bit_length())
            padded = np.zeros((width, seg.shape[1]), seg.dtype)
            padded[:n_seg] = seg
            d, li = scan_fn(
                jnp.asarray(luts[pi]),
                jnp.asarray(padded),
                jnp.asarray(np.arange(width) < n_seg),
            )
            d = np.asarray(d)[:kk]
            gi = index.cluster_ids(probe[pi])[np.asarray(li)[:kk]]
            md = np.concatenate([best_d, d])
            mi = np.concatenate([best_i, gi])
            sel = np.argsort(md, kind="stable")[:k]
            best_d, best_i = md[sel], mi[sel]
        out_d[qi], out_i[qi] = best_d, best_i
    return out_d, out_i


def brute_force(
    xs: np.ndarray, queries: np.ndarray, k: int
) -> tuple[np.ndarray, np.ndarray]:
    """Exact k-NN ground truth for recall tests."""
    d2 = np.asarray(
        _pairwise_sq_l2(jnp.asarray(queries, jnp.float32), jnp.asarray(xs, jnp.float32))
    )
    idx = np.argsort(d2, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d2, idx, axis=1), idx


def recall_at_k(found_ids: np.ndarray, true_ids: np.ndarray) -> float:
    """recall@k: |found ∩ true| / k averaged over queries."""
    hits = 0
    for f, t in zip(found_ids, true_ids):
        hits += len(set(f.tolist()) & set(t.tolist()))
    return hits / true_ids.size
