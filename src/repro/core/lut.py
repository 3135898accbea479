"""Lookup-table (LUT) construction -- online stage (b) of IVFPQ.

For a query q and a probed cluster with centroid c, LUT[m, j] is the squared
L2 distance between the m-th subsegment of (q - c) and codeword j of
sub-codebook B_m.  ADC then scores a point with codes e as
    L2(q, x) ~= sum_m LUT[m, e_m].

On UPMEM the LUT lives in WRAM (8 KB for M=16 uint16 entries); on TPU the
scan kernels pin it in VMEM.  This module is the one LUT build of every path:
the host reference and the device step (`kernels.ops.build_luts`) alike.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def build_lut(codebook: jax.Array, q_minus_c: jax.Array) -> jax.Array:
    """LUT for one (query, cluster) pair.

    Args:
      codebook: (M, 256, d_sub).
      q_minus_c: (D,) residual of the query w.r.t. the probed centroid.

    Returns:
      (M, 256) float32 table of partial squared distances.
    """
    m, ncodes, dsub = codebook.shape
    qr = q_minus_c.reshape(m, 1, dsub)
    diff = codebook - qr                     # (M, 256, dsub)
    return jnp.sum(diff * diff, axis=-1)     # (M, 256)


@jax.jit
def build_luts(codebook: jax.Array, q_minus_c: jax.Array) -> jax.Array:
    """Batched LUTs: q_minus_c (B, D) -> (B, M, 256)."""
    return jax.vmap(lambda r: build_lut(codebook, r))(q_minus_c)
