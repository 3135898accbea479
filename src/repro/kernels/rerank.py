"""Pallas exact re-rank kernel: full-precision distances for ADC survivors.

Second stage of the retrieval cascade (FusionANNS-style PQ -> full-precision
re-rank): the fused ADC scan overfetches k' >> k candidates by quantized
distance, then this kernel recomputes their distances exactly against the raw
vectors gathered from the per-device raw-vector shard.  On the DPU analogue
this is the small full-precision pass the paper's host CPU performs on the
merged candidate set; here it is one grid step per query over a (k', D)
candidate block.

Layout notes:
  * candidates reach the kernel already gathered (Q, K, D) -- the gather by
    candidate id happens in the shard_map step, where each device owns the
    rows of its home clusters (see retrieval.layout.RawStore);
  * one (1, block_k) lane-dense output block per grid step: queries and
    outputs carry a singleton middle axis so every block's last two dims
    are either full or (8, 128)-aligned, as Mosaic requires;
  * distances are accumulated in f32 regardless of the storage dtype: a
    bf16 raw shard still yields f32 sums over bf16-rounded coordinates,
    which keeps the selection contract deterministic (see ops.rerank_dists).

The matching oracle is `ref.rerank_dists_ref` (allclose, like every kernel
in this package).  The cascade's end-to-end *bit*-identity contract
(`tests/test_rerank.py`) is pinned against this kernel itself: a brute-force
fp32 re-rank of the same candidate set through `ops.rerank_dists` at the
same (Q, K, D) shape reproduces the sharded cascade bit-for-bit, because
each output element's reduction reads only its own (q, k, :) slice.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rerank_dists_block(q_ref, cand_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)          # (1, D)
    cand = cand_ref[...].astype(jnp.float32)    # (Kb, D)
    diff = cand - q                             # broadcast over Kb candidates
    # sum over D down the sublanes of the transpose: lane-dense (1, Kb)
    out_ref[...] = jnp.sum((diff * diff).T, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def rerank_dists_kernel(
    queries: jax.Array,
    cand: jax.Array,
    *,
    block_k: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """(Q, D) queries x (Q, K, D) gathered candidates -> (Q, K) f32 sq-L2.

    `cand` may be f32 or bf16 (the raw-shard storage dtype); coordinates are
    widened to f32 before the subtract, so the result is the exact f32
    squared distance to the *stored* vector.

    `block_k` splits the candidate axis into (K / block_k) grid steps of
    `block_k` candidates each (0 = one step over the whole axis; K must be
    a `block_k` multiple -- ops.rerank_dists pads it).  Every output
    element's reduction reads only its own (q, k, :) slice, so the result
    is bit-identical at every block_k: the knob trades VMEM block footprint
    against grid-step overhead and is safe for the autotuner to sweep.
    """
    q, d = queries.shape
    k = cand.shape[1]
    bk = block_k or k
    if k % bk:
        raise ValueError(
            f"rerank_dists_kernel: K={k} not a multiple of block_k={bk}"
        )
    out = pl.pallas_call(
        _rerank_dists_block,
        grid=(q, k // bk),
        in_specs=[
            pl.BlockSpec((None, 1, d), lambda qi, ki: (qi, 0, 0)),
            pl.BlockSpec((None, bk, d), lambda qi, ki: (qi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, bk), lambda qi, ki: (qi, 0, ki)),
        out_shape=jax.ShapeDtypeStruct((q, 1, k), jnp.float32),
        interpret=interpret,
    )(queries.reshape(q, 1, d), cand)
    return out.reshape(q, k)
