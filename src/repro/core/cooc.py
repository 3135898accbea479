"""§4.3 Co-occurrence-aware encoding: mine frequent positioned code
combinations, cache their partial sums after LUT construction, and re-encode
vectors with *direct addresses* into the flat [LUT | combo-sums] table.

Positioned item = (column m, codeword j); a combo only matches when all its
items appear at their exact columns (the paper's positional constraint).

Offline (host, numpy):
  mine_combos()    -- ICG-flavoured greedy miner (pair counting -> extension)
  reencode()       -- rewrite (N, M) uint8 codes into (N, W) flat addresses;
                      matched length-3 combos shrink 3 entries to 1

Online (JAX):
  build_ext_lut()  -- LUT -> flat [LUT (M*256) | combo partial sums (m) | 0]
  adc_scan_flat()  -- (in core/search.py) distance = sum(ext_lut[addrs])

Direct addressing kills the `j + 256*m` index arithmetic inside the scan loop
(on UPMEM because DPU multiplies are slow; on TPU because the flat address is
exactly the gather/one-hot index the kernel wants).

Invariant (tested): the flat scan reproduces the plain ADC distances bit-for-
bit up to float addition reordering -- the optimization never changes recall
(paper §5.1: "The optimizations in MemANNS do not impact the recall").
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

NCODES = 256


@dataclasses.dataclass
class ComboSet:
    """Mined co-occurrence combinations (one set per cluster or global).

    Attributes:
      cols: (m, L) int32 columns of each combo.
      codes: (m, L) int32 codeword ids at those columns.
      support: (m,) int64 number of training rows matching each combo.
    """

    cols: np.ndarray
    codes: np.ndarray
    support: np.ndarray

    @property
    def n_combos(self) -> int:
        return self.cols.shape[0]

    @property
    def combo_len(self) -> int:
        return self.cols.shape[1]


@dataclasses.dataclass
class CoocCodes:
    """Re-encoded (direct-address) code matrix for one shard of vectors.

    addrs[n, :lengths[n]] are flat indices into the extended LUT; the rest is
    the zero-sentinel address.  Total table size A = M*256 + m + 1 (< 2^16 for
    the paper's M=16, m=256 => addresses fit uint16, honoured here by
    asserting and storing uint16 like the paper; widened in-kernel to int32).
    """

    addrs: np.ndarray  # (N, W) uint16
    lengths: np.ndarray  # (N,) int32
    m_subspaces: int
    n_combos: int

    @property
    def table_size(self) -> int:
        return self.m_subspaces * NCODES + self.n_combos + 1

    @property
    def sentinel(self) -> int:
        return self.table_size - 1

    @property
    def width(self) -> int:
        return self.addrs.shape[1]

    def length_reduction(self) -> float:
        """Average code length reduction (paper Table 1's x-axis)."""
        return 1.0 - float(self.lengths.mean()) / self.m_subspaces


def mine_combos(
    codes: np.ndarray,
    n_combos: int = 256,
    combo_len: int = 3,
    top_pairs: int | None = None,
    max_rows: int = 200_000,
    min_support: int = 2,
    seed: int = 0,
) -> ComboSet:
    """Greedy ICG miner: positioned-pair counting, then best-third extension.

    The paper builds an Item Co-occurrence Graph over positioned items and
    clusters it (GRACE [49]); we implement the same objective -- maximise
    total matched support of m combos of length `combo_len` -- with a direct
    frequent-pair -> greedy-extension scheme that needs no graph library.
    """
    codes = np.asarray(codes)
    n, m = codes.shape
    if n == 0:
        z = np.zeros((0, combo_len), np.int32)
        return ComboSet(cols=z, codes=z.copy(), support=np.zeros(0, np.int64))
    if n > max_rows:
        sel = np.random.default_rng(seed).choice(n, max_rows, replace=False)
        codes = codes[sel]
        n = max_rows
    if top_pairs is None:
        top_pairs = 4 * n_combos

    # positioned item ids col*256 + code; pair keys fit int32 for m <= 181
    pid = np.arange(m, dtype=np.int32) * NCODES + codes.astype(np.int32)
    key_t = np.int32 if (m * NCODES) ** 2 < 2**31 else np.int64
    # --- 1. count positioned pairs over all column pairs -------------------
    c1s, c2s = np.triu_indices(m, 1)
    keys = pid[:, c1s].astype(key_t) * (m * NCODES) + pid[:, c2s]
    uniq, counts = np.unique(keys, return_counts=True)
    order = np.argsort(-counts, kind="stable")[:top_pairs]
    uniq, counts = uniq[order], counts[order]

    # --- 2. extend each frequent pair with its best third item -------------
    out_cols: list[tuple[int, ...]] = []
    out_codes: list[tuple[int, ...]] = []
    out_sup: list[int] = []
    seen: set[tuple] = set()
    for key, cnt in zip(uniq, counts):
        if cnt < min_support or len(out_sup) >= n_combos:
            break
        pid2 = int(key % (m * NCODES))
        pid1 = int(key // (m * NCODES))
        c1, j1 = divmod(pid1, NCODES)
        c2, j2 = divmod(pid2, NCODES)
        rows = (codes[:, c1] == j1) & (codes[:, c2] == j2)
        if combo_len == 2:
            sig = ((c1, j1), (c2, j2))
            if sig not in seen:
                seen.add(sig)
                out_cols.append((c1, c2))
                out_codes.append((j1, j2))
                out_sup.append(int(cnt))
            continue
        # best third positioned item among remaining columns: the most
        # frequent code per column, the first column on ties
        bc = np.bincount(pid[rows].ravel(), minlength=m * NCODES)
        bc = bc.reshape(m, NCODES)
        j3s = bc.argmax(axis=1)
        sup = bc[np.arange(m), j3s]
        sup[[c1, c2]] = -1
        c3 = int(sup.argmax())
        sup3, j3 = int(sup[c3]), int(j3s[c3])
        if sup3 < min_support:
            continue
        tri = sorted([(c1, j1), (c2, j2), (c3, j3)])
        sig = tuple(tri)
        if sig in seen:
            continue
        seen.add(sig)
        out_cols.append(tuple(t[0] for t in tri))
        out_codes.append(tuple(t[1] for t in tri))
        out_sup.append(sup3)

    if not out_sup:
        z = np.zeros((0, combo_len), np.int32)
        return ComboSet(cols=z, codes=z.copy(), support=np.zeros(0, np.int64))
    order = np.argsort(-np.asarray(out_sup), kind="stable")
    return ComboSet(
        cols=np.asarray(out_cols, np.int32)[order],
        codes=np.asarray(out_codes, np.int32)[order],
        support=np.asarray(out_sup, np.int64)[order],
    )


# rows matched against every combo at once in `reencode` (bounds the
# (rows, n_combos) match matrix)
_REENCODE_ROWS = 1 << 15


def reencode(
    codes: np.ndarray,
    combos: ComboSet,
    width: int | None = None,
) -> CoocCodes:
    """Rewrite uint8 codes as direct addresses, substituting matched combos.

    Greedy, support-ordered, non-overlapping (a column consumed by one combo
    cannot join another -- the paper's example works the same way).

    Args:
      codes: (N, M) uint8.
      width: fixed output width; default M (worst case, no combo matched).

    Returns:
      CoocCodes with addrs (N, width) uint16.
    """
    codes = np.asarray(codes)
    n, m = codes.shape
    n_combos = combos.n_combos
    table = m * NCODES + n_combos + 1
    assert table <= 65536, "direct addresses must fit uint16 (paper §4.3)"
    sentinel = table - 1

    # column-major working copies: one column's rows are contiguous
    codes_t = np.ascontiguousarray(codes.T)
    # base: direct address col*256 + code (original items, uint16 in paper)
    addr_t = np.arange(m, dtype=np.int32)[:, None] * NCODES + codes_t
    removed_t = np.zeros((m, n), bool)
    # columns consumed by an applied combo (anchor AND elided): a later combo
    # may not reuse any of them -- otherwise it would overwrite the anchor
    # address or elide it (hypothesis-found bug: overlapping anchors)
    used_t = np.zeros((m, n), bool)

    cols, ccodes = combos.cols, combos.codes
    # padding/dummy combos (duplicate columns) never match
    distinct = np.asarray(
        [len(set(c.tolist())) == len(c) for c in cols], bool
    )
    # each row's re-encoding depends on that row alone: match in row chunks
    for r0 in range(0, n, _REENCODE_ROWS):
        rs = slice(r0, min(n, r0 + _REENCODE_ROWS))
        match = np.repeat(distinct[:, None], rs.stop - r0, axis=1)
        for t in range(cols.shape[1]):
            match &= codes_t[cols[:, t], rs] == ccodes[:, t, None]
        for s in np.flatnonzero(match.any(axis=1)):  # support order
            ccols = cols[s]
            ok = match[s] & ~used_t[ccols, rs].any(axis=0)
            if not ok.any():
                continue
            rows = np.flatnonzero(ok) + r0
            # first column carries the combo address; the rest are elided
            addr_t[ccols[0], rows] = m * NCODES + s
            removed_t[ccols[1:, None], rows] = True
            used_t[ccols[:, None], rows] = True
    addr, removed = addr_t.T, removed_t.T

    keep = ~removed
    lengths = keep.sum(axis=1).astype(np.int32)
    w = int(width) if width is not None else m
    assert w >= int(lengths.max(initial=0)), "width too small for re-encoding"
    order = np.argsort(removed, axis=1, kind="stable")  # kept entries first
    packed = np.take_along_axis(addr, order, axis=1)[:, :w]
    mask = np.arange(w)[None, :] < lengths[:, None]
    packed = np.where(mask, packed, sentinel).astype(np.uint16)
    return CoocCodes(
        addrs=packed, lengths=lengths, m_subspaces=m, n_combos=n_combos
    )


def plain_to_flat(codes: np.ndarray, n_combos: int = 0) -> np.ndarray:
    """Baseline direct-address form of plain codes (no combos), uint16."""
    n, m = codes.shape
    return (
        np.arange(m)[None, :] * NCODES + codes.astype(np.int32)
    ).astype(np.uint16)


def build_ext_lut(
    lut: jax.Array, combo_cols: jax.Array, combo_codes: jax.Array
) -> jax.Array:
    """Online: flat [LUT row-major | combo partial sums | zero sentinel].

    jit-safe; shapes static.  This is the paper's "reserve a buffer after the
    LUT, pre-arranged layout" -- combo s lives at flat address M*256 + s.
    """
    sums = jnp.sum(
        lut[combo_cols, combo_codes], axis=-1
    )  # (m,) partial sums from the constructed LUT
    zero = jnp.zeros((1,), lut.dtype)
    return jnp.concatenate([lut.reshape(-1), sums.astype(lut.dtype), zero])


def max_combo_frequency(
    codes: np.ndarray, lengths: tuple[int, ...] = (3, 4, 5), max_rows: int = 100_000
) -> dict[int, float]:
    """Paper Fig. 10: max co-occurrence frequency of combos per length.

    Returns length -> max fraction of rows sharing one positioned combination
    (computed over contiguous column windows, a lower bound on the true max).
    """
    codes = np.asarray(codes)
    n, m = codes.shape
    if n == 0:
        return {l: 0.0 for l in lengths}
    if n > max_rows:
        codes = codes[
            np.random.default_rng(0).choice(n, max_rows, replace=False)
        ]
        n = max_rows
    out: dict[int, float] = {}
    for l in lengths:
        best = 0
        for c0 in range(0, m - l + 1):
            window = codes[:, c0 : c0 + l].astype(np.int64)
            key = np.zeros(n, np.int64)
            for t in range(l):
                key = key * NCODES + window[:, t]
            _, counts = np.unique(key, return_counts=True)
            best = max(best, int(counts.max()))
        out[l] = best / n
    return out
