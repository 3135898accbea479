"""Pure-jnp oracles for every Pallas kernel in this package.

Each function mirrors the exact contract of its kernel counterpart; tests
sweep shapes/dtypes and assert allclose(kernel, ref).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NCODES = 256


def adc_scan_ref(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """(M, 256) x (N, M) -> (N,) ADC distances."""
    m = lut.shape[0]
    cols = jnp.arange(m)
    return jnp.sum(lut[cols[None, :], codes.astype(jnp.int32)], axis=-1)


def adc_scan_flat_ref(ext_lut: jax.Array, addrs: jax.Array) -> jax.Array:
    """(A,) x (N, W) direct-address scan -> (N,)."""
    return jnp.sum(ext_lut[addrs.astype(jnp.int32)], axis=-1)


def adc_topk_ref(
    lut: jax.Array, codes: jax.Array, k: int, n_valid: jax.Array | int | None = None
) -> tuple[jax.Array, jax.Array]:
    """Fused scan + k smallest.  luts (Q, M, 256), codes (N, M) ->
    (Q, k) values, (Q, k) int32 indices (ascending by distance)."""
    d = jax.vmap(lambda l: adc_scan_ref(l, codes))(lut)  # (Q, N)
    if n_valid is not None:
        valid = jnp.arange(codes.shape[0]) < n_valid
        d = jnp.where(valid[None, :], d, jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx.astype(jnp.int32)


def adc_topk_flat_ref(
    ext_lut: jax.Array,
    addrs: jax.Array,
    k: int,
    n_valid: jax.Array | int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Direct-address fused scan + top-k.  ext_lut (Q, A), addrs (N, W)."""
    d = jax.vmap(lambda e: adc_scan_flat_ref(e, addrs))(ext_lut)  # (Q, N)
    if n_valid is not None:
        valid = jnp.arange(addrs.shape[0]) < n_valid
        d = jnp.where(valid[None, :], d, jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx.astype(jnp.int32)


def rerank_dists_ref(queries: jax.Array, cand: jax.Array) -> jax.Array:
    """(Q, D) x (Q, K, D) -> (Q, K) exact f32 squared-L2 distances.

    Mirrors `rerank.rerank_dists_kernel`'s contract (f32 widening, one sum
    over the trailing coordinate axis); tests assert allclose like every
    other kernel here.  The cascade's *bit*-identity contract is pinned
    against the kernel itself (`ops.rerank_dists` on the same candidate
    shape), because XLA reduces different array shapes in different f32
    orders even for the same math.
    """
    diff = cand.astype(jnp.float32) - queries.astype(jnp.float32)[:, None, :]
    return jnp.sum(diff * diff, axis=-1)


def lut_build_ref(codebook: jax.Array, qmc: jax.Array) -> jax.Array:
    """(M, 256, dsub) x (Q, M, dsub) -> (Q, M, 256) squared-L2 LUTs."""
    diff = qmc[:, :, None, :] - codebook[None, :, :, :]
    return jnp.sum(diff * diff, axis=-1)


def ext_lut_build_ref(
    lut: jax.Array, combo_cols: jax.Array, combo_codes: jax.Array
) -> jax.Array:
    """(Q, M, 256) + combos (m, L) -> (Q, M*256 + m + 1) flat tables."""
    q = lut.shape[0]
    sums = jnp.sum(lut[:, combo_cols, combo_codes], axis=-1)  # (Q, m)
    zero = jnp.zeros((q, 1), lut.dtype)
    return jnp.concatenate([lut.reshape(q, -1), sums, zero], axis=-1)


def merge_topk_rounds_ref(
    cur_v: jax.Array,
    cur_i: jax.Array,
    dists: jax.Array,
    ids: jax.Array,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """k smallest of [cur | tile] in stable (value, position) order, by k
    rounds of re-selection (the oracle of `adc_topk.merge_admitted`).

    cur_v / cur_i: (1, k) running top-k, ascending; dists / ids: (1, BN)
    tile candidates.  Running entries take positions 0..k-1 and tile rows
    k + lane, so each round's pick -- the smallest (value, position) pair
    above the previous pick -- reproduces a stable ascending sort.
    """
    big = jnp.iinfo(jnp.int32).max
    pc = jax.lax.broadcasted_iota(jnp.int32, cur_v.shape, 1)
    pt = jax.lax.broadcasted_iota(jnp.int32, dists.shape, 1) + k

    def rmin(x):
        return jnp.min(x, axis=1, keepdims=True)

    def pick(j, carry):
        out_v, out_i, lv, lp = carry
        ac = (cur_v > lv) | ((cur_v == lv) & (pc > lp))
        at = (dists > lv) | ((dists == lv) & (pt > lp))
        m = jnp.minimum(
            rmin(jnp.where(ac, cur_v, jnp.inf)),
            rmin(jnp.where(at, dists, jnp.inf)),
        )
        p = jnp.minimum(
            rmin(jnp.where(ac & (cur_v == m), pc, big)),
            rmin(jnp.where(at & (dists == m), pt, big)),
        )
        sel = jnp.minimum(
            rmin(jnp.where(pc == p, cur_i, big)),
            rmin(jnp.where(pt == p, ids, big)),
        )
        return (
            jnp.where(pc == j, m, out_v), jnp.where(pc == j, sel, out_i),
            m, p,
        )

    init = (
        cur_v, cur_i,
        jnp.full((1, 1), -jnp.inf, cur_v.dtype),
        jnp.full((1, 1), -1, jnp.int32),
    )
    out_v, out_i, _, _ = jax.lax.fori_loop(0, k, pick, init)
    return out_v, out_i
