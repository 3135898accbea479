"""The plain reference: IVF-PQ search with an exact re-rank.

It imports nothing of the program.  From the program's build it takes
the trained quantizer alone -- coarse centroids and PQ codebooks -- since
k-means training has no single answer to reproduce.  Everything built
from the quantizer it works out itself from the raw vectors the corpus
was made of (`build_index`, on the device in blocks, in f32 at full
precision):

  * each row's list: its nearest centroid;
  * each row's PQ code: per subspace, the nearest codeword of its
    residual to that centroid.

The program's lists and codes are judged against these: a row whose list
or code differs only at a tie (distances within `tie` of the nearest,
relative to |x|^2 + |c|^2, the scale of an f32 rounding of the distance)
keeps the program's choice; any other difference counts as `misassigned`
or `miscoded`, and a row missing from the lists or held twice as
`lost_rows`.  The reference index keeps the nearest choice there.

For one query (`truth`) it works out what any correct IVF-PQ answer over
the reference index may and must hold, so that ids at tied distances are
judged fairly:

  * probes: the `nprobe` clusters whose centroids are nearest, by exact
    float64 distances.  Clusters within `probe_tie` (relative to
    |q|^2 + |c|^2) of the nprobe-th distance are optional: either side of
    such a near-tie is a correct probe set;
  * ADC: every row of the required and optional clusters, scored with
    f32 look-up tables (|q - c - codeword|^2 per subspace) summed in f32;
  * candidates: the `k_cand` smallest ADC distances.  Rows below the
    k_cand-th distance by more than `adc_tie` (relative) are required,
    rows up to `adc_tie` above it are admissible;
  * with a re-rank, the exact squared L2 distance of each admissible
    candidate to the raw vector, in integers (the source values are
    integers, so these distances have no rounding at all).

`precision="bfloat16"` computes the same in bfloat16 (bf16 inputs, f32
accumulation for distances through a matrix product and for the ADC
sums, bf16 for the tables and the exact distances): the control that has
to fail.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16


@dataclasses.dataclass
class PlainIndex:
    """Trained IVF-PQ index as plain arrays (CSR by cluster)."""

    centroids: np.ndarray   # (C, D) f32
    codebook: np.ndarray    # (M, 256, dsub) f32
    codes: np.ndarray       # (N, M) uint8, rows of cluster c at offsets[c]:
    ids: np.ndarray         # (N,) int64 vector ids, same order
    offsets: np.ndarray     # (C + 1,) int64

    def rows_of(self, clusters: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(row positions, owning slot in `clusters`) of the clusters'
        rows, in cluster order."""
        starts, ends = self.offsets[clusters], self.offsets[clusters + 1]
        lens = ends - starts
        slot = np.repeat(np.arange(len(clusters)), lens)
        first = np.repeat(starts - np.cumsum(lens) + lens, lens)
        return first + np.arange(int(lens.sum())), slot


def csr_index(centroids: np.ndarray, codebook: np.ndarray,
              assign: np.ndarray, codes: np.ndarray) -> PlainIndex:
    """A PlainIndex from each row's list and code (rows in id order)."""
    order = np.argsort(assign, kind="stable")
    counts = np.bincount(assign, minlength=centroids.shape[0])
    return PlainIndex(centroids=centroids, codebook=codebook,
                      codes=codes[order], ids=order.astype(np.int64),
                      offsets=np.concatenate([[0], np.cumsum(counts)]))


def rows_of_index(index: PlainIndex, n: int):
    """(list of each row id, its code, held exactly once) of an index's
    CSR arrays; a row held twice keeps its last entry."""
    c_n = index.offsets.shape[0] - 1
    owner = np.repeat(np.arange(c_n, dtype=np.int32), np.diff(index.offsets))
    inside = (index.ids >= 0) & (index.ids < n)
    ids = index.ids[inside]
    held = np.bincount(ids, minlength=n)[:n]
    assign = np.zeros(n, np.int32)
    codes = np.zeros((n, index.codes.shape[1]), np.uint8)
    assign[ids] = owner[inside]
    codes[ids] = index.codes[inside]
    lost = int(np.sum(held != 1)) + int(np.sum(~inside))
    return assign, codes, held == 1, lost


def _jnp_precision(precision: str):
    return (jax.lax.Precision.DEFAULT if precision == "bfloat16"
            else jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("precision",))
def _nearest(x, cent, codebook, precision):
    """Nearest centroid of each row, then nearest codeword of each
    subspace of its residual (the reference's own build of a block)."""
    a = _argmin_sq(x, cent, precision)
    return a, _encode(x - cent[a], codebook, precision)


def _cast(x, precision):
    return x.astype(jnp.bfloat16) if precision == "bfloat16" else x


def _argmin_sq(x, c, precision):
    """argmin over rows of c of |x - c|^2 by |x|^2 - 2 x.c + |c|^2."""
    xc = jnp.dot(_cast(x, precision), _cast(c, precision).T,
                 precision=_jnp_precision(precision),
                 preferred_element_type=jnp.float32)
    return jnp.argmin(jnp.sum(c * c, 1)[None] - 2.0 * xc, axis=1)


def _encode(r, codebook, precision):
    """(B, M) nearest codeword of each subspace of residuals r (B, D)."""
    m, ncodes, dsub = codebook.shape
    sub = r.reshape(r.shape[0], m, dsub)
    rc = jnp.einsum("bmd,mkd->bmk", _cast(sub, precision),
                    _cast(codebook, precision),
                    precision=_jnp_precision(precision),
                    preferred_element_type=jnp.float32)
    cb2 = jnp.sum(codebook * codebook, -1)                   # (M, 256)
    return jnp.argmin(cb2[None] - 2.0 * rc, axis=-1)


@jax.jit
def _judge_rows(x, cent, codebook, a_prog, code_prog, held, tie):
    """Judge one block of the program's lists and codes against the
    nearest choices; returns (reference list, reference code,
    misassigned rows, miscoded subcodes)."""
    m, ncodes, dsub = codebook.shape
    a_best = _argmin_sq(x, cent, "float32")
    d_prog = jnp.sum((x - cent[a_prog]) ** 2, 1)
    d_best = jnp.sum((x - cent[a_best]) ** 2, 1)
    scale = jnp.sum(x * x, 1) + jnp.sum(cent[a_best] ** 2, 1)
    ok_a = held & (d_prog <= d_best + tie * scale)
    a_ref = jnp.where(ok_a, a_prog, a_best)
    r = x - cent[a_ref]
    best = _encode(r, codebook, "float32")                   # (B, M)
    sub = r.reshape(r.shape[0], m, dsub)
    cols = jnp.arange(m)[None]
    w_prog = codebook[cols, code_prog]                       # (B, M, dsub)
    w_best = codebook[cols, best]
    dc_prog = jnp.sum((sub - w_prog) ** 2, -1)
    dc_best = jnp.sum((sub - w_best) ** 2, -1)
    scale_c = jnp.sum(sub * sub, -1) + jnp.sum(w_best * w_best, -1)
    ok_c = dc_prog <= dc_best + tie * scale_c
    keep = ok_a[:, None] & ok_c
    code_ref = jnp.where(keep, code_prog, best)
    return (a_ref, code_ref, jnp.sum(held & ~ok_a),
            jnp.sum(ok_a[:, None] & ~ok_c))


def _blocks(n: int, block: int):
    for s in range(0, n, block):
        yield s, min(block, n - s)


def _padded(a: np.ndarray, rows: int) -> np.ndarray:
    if a.shape[0] == rows:
        return a
    pad = np.zeros((rows - a.shape[0],) + a.shape[1:], a.dtype)
    return np.concatenate([a, pad])


def own_index(centroids: np.ndarray, codebook: np.ndarray, base: np.ndarray,
              precision: str = "float32", block: int = 1 << 14
              ) -> PlainIndex:
    """The index the quantizer gives the corpus, nearest choices only,
    computed in `precision` ("bfloat16": the control's build)."""
    n = base.shape[0]
    cent, cb = jnp.asarray(centroids), jnp.asarray(codebook)
    assign = np.empty(n, np.int32)
    codes = np.empty((n, codebook.shape[0]), np.uint8)
    for s, b in _blocks(n, block):
        x = jnp.asarray(_padded(base[s:s + b], block), jnp.float32)
        a, c = _nearest(x, cent, cb, precision)
        assign[s:s + b] = np.asarray(a)[:b]
        codes[s:s + b] = np.asarray(c)[:b]
    return csr_index(centroids, codebook, assign, codes)


def build_index(program: PlainIndex, base: np.ndarray, tie: float,
                block: int = 1 << 14) -> tuple[PlainIndex, dict]:
    """The reference index from the program's quantizer and the raw
    vectors, and how far the program's own lists and codes depart from
    it: (index, {"misassigned", "miscoded", "lost_rows"})."""
    n = base.shape[0]
    a_prog, c_prog, held, lost = rows_of_index(program, n)
    cent = jnp.asarray(program.centroids)
    cb = jnp.asarray(program.codebook)
    assign = np.empty(n, np.int32)
    codes = np.empty((n, program.codebook.shape[0]), np.uint8)
    misassigned = miscoded = 0
    for s, b in _blocks(n, block):
        a, c, bad_a, bad_c = _judge_rows(
            jnp.asarray(_padded(base[s:s + b], block), jnp.float32), cent,
            cb, jnp.asarray(_padded(a_prog[s:s + b], block)),
            jnp.asarray(_padded(c_prog[s:s + b], block).astype(np.int32)),
            jnp.asarray(_padded(held[s:s + b], block)), np.float32(tie))
        assign[s:s + b] = np.asarray(a)[:b]
        codes[s:s + b] = np.asarray(c)[:b]
        misassigned += int(bad_a)
        miscoded += int(bad_c)
    index = csr_index(program.centroids, program.codebook, assign, codes)
    return index, {"misassigned": misassigned, "miscoded": miscoded,
                   "lost_rows": lost}


@dataclasses.dataclass
class QueryTruth:
    """What a correct answer to one query may and must hold."""

    admissible: dict        # id -> reference distance (ADC or exact)
    required: np.ndarray    # ids every correct candidate set holds
    required_dist: np.ndarray
    exact: bool             # distances are exact (re-rank) or ADC


def _probes(index: PlainIndex, q: np.ndarray, nprobe: int, probe_tie: float,
            precision: str) -> tuple[np.ndarray, np.ndarray]:
    c = index.centroids
    if precision == "bfloat16":
        qb = q.astype(BF16).astype(np.float32)
        cb = c.astype(BF16).astype(np.float32)
        d = (np.sum(qb * qb) - 2.0 * (cb @ qb) + np.sum(cb * cb, axis=1))
        d = d.astype(np.float64)
    else:
        diff = c.astype(np.float64) - q.astype(np.float64)
        d = np.einsum("cd,cd->c", diff, diff)
    order = np.argsort(d, kind="stable")
    edge = order[nprobe - 1]
    scale = float(np.sum(q.astype(np.float64) ** 2)
                  + np.sum(c[edge].astype(np.float64) ** 2))
    tie = probe_tie * scale
    required = np.flatnonzero(d < d[edge] - tie)
    optional = np.flatnonzero(np.abs(d - d[edge]) <= tie)
    if len(required) + len(optional) < nprobe:  # bf16 may tie widely
        required = order[:nprobe]
        optional = np.zeros(0, np.int64)
    return required, optional


def _adc(index: PlainIndex, q: np.ndarray, clusters: np.ndarray,
         precision: str) -> tuple[np.ndarray, np.ndarray]:
    """(ids, ADC distances) of every row of `clusters`."""
    m, ncodes, dsub = index.codebook.shape
    if len(clusters) == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.float32)
    qmc = (q[None, :] - index.centroids[clusters]).astype(np.float32)
    sub = qmc.reshape(len(clusters), m, 1, dsub)
    if precision == "bfloat16":
        diff = index.codebook[None].astype(BF16) - sub.astype(BF16)
        lut = np.sum(diff * diff, axis=-1, dtype=BF16).astype(np.float32)
    else:
        diff = index.codebook[None] - sub                   # f32
        lut = np.sum(diff * diff, axis=-1, dtype=np.float32)
    rows, slot = index.rows_of(clusters)
    codes = index.codes[rows].astype(np.int64)              # (R, M)
    flat = lut.reshape(len(clusters), m * ncodes)
    addr = np.arange(m) * ncodes + codes                    # (R, M)
    dist = np.sum(flat[slot[:, None], addr], axis=1, dtype=np.float32)
    return index.ids[rows], dist


def _exact(base: np.ndarray, q: np.ndarray, ids: np.ndarray,
           precision: str) -> np.ndarray:
    if precision == "bfloat16":
        diff = base[ids].astype(BF16) - q.astype(BF16)
        return np.sum((diff * diff).astype(BF16), axis=1,
                      dtype=BF16).astype(np.float64)
    diff = base[ids].astype(np.int64) - q.astype(np.int64)
    return np.sum(diff * diff, axis=1).astype(np.float64)


def truth(index: PlainIndex, base: np.ndarray, q: np.ndarray, *,
          nprobe: int, k: int, k_cand: int, rerank: bool,
          probe_tie: float, adc_tie: float) -> QueryTruth:
    """The f32 reference's judgement material for one query."""
    req_c, opt_c = _probes(index, q, nprobe, probe_tie, "float32")
    ids_r, adc_r = _adc(index, q, req_c, "float32")
    ids_o, adc_o = _adc(index, q, opt_c, "float32")
    ids = np.concatenate([ids_r, ids_o])
    adc = np.concatenate([adc_r, adc_o]).astype(np.float64)
    inf = np.inf
    # k_cand-th distance with every optional cluster in (the least it can
    # be) and with none (the most it can be)
    lo = np.sort(adc)[k_cand - 1] if len(adc) >= k_cand else inf
    hi = np.sort(adc_r)[k_cand - 1] if len(adc_r) >= k_cand else inf
    must = adc[: len(ids_r)] < lo * (1.0 - adc_tie)
    may = adc <= hi * (1.0 + adc_tie)
    adm_ids = ids[may]
    req_ids = ids_r[must]
    if rerank:
        adm_d = _exact(base, q, adm_ids, "float32")
        req_d = _exact(base, q, req_ids, "float32")
    else:
        adm_d = adc[may]
        req_d = adc[: len(ids_r)][must]
    return QueryTruth(admissible=dict(zip(adm_ids.tolist(), adm_d.tolist())),
                      required=req_ids, required_dist=req_d, exact=rerank)


def answer(index: PlainIndex, base: np.ndarray, q: np.ndarray, *,
           nprobe: int, k: int, k_cand: int, rerank: bool,
           precision: str) -> tuple[np.ndarray, np.ndarray]:
    """The reference's own top-k (dists, ids) in `precision`; with
    "bfloat16" this is the control put in the program's place."""
    req_c, opt_c = _probes(index, q, nprobe, 0.0, precision)
    clusters = np.concatenate([req_c, opt_c])[:nprobe]
    ids, adc = _adc(index, q, clusters, precision)
    sel = np.argsort(adc, kind="stable")[:k_cand]
    ids, dist = ids[sel], adc[sel].astype(np.float64)
    if rerank:
        dist = _exact(base, q, ids, precision)
        sel = np.argsort(dist, kind="stable")[:k]
        ids, dist = ids[sel], dist[sel]
    out_d = np.full(k, np.inf)
    out_i = np.full(k, -1, np.int64)
    out_d[:len(ids)], out_i[:len(ids)] = dist[:k], ids[:k]
    return out_d, out_i
