"""Pallas kernel: the fused extended-table build ([LUT | combo partial sums
| 0], paper §4.3 online part).

On the DPU, threads build LUT segments from the codebook and then compute the
combo partial sums into a pre-arranged WRAM buffer.  Here the LUT itself is
one fused XLA reduction (`core.lut.build_luts`, the same on every backend);
this kernel keeps one query's LUT in VMEM as (2M, 128) chunk rows -- the
chunk layout every scan kernel reads (`adc_scan.as_chunks`) -- and appends
the combo sums, gathered with the scan's own lane-gather helper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.adc_scan import LANE, _gather_dists

NCODES = 256


def _ext_lut_kernel(lut_ref, caddr_ref, out_ref, *, n_combos: int):
    n_lut = lut_ref.shape[0]
    out_ref[pl.ds(0, n_lut), :] = lut_ref[...]
    # combo sums: the L items of each combo down the sublanes, combos along
    # lanes -- the scan's (W, BN) address layout with W = L
    sums = _gather_dists(lut_ref, caddr_ref[...], segmented=False)
    lane = jax.lax.broadcasted_iota(jnp.int32, sums.shape, 1)
    sums = jnp.where(lane < n_combos, sums, 0.0)  # sentinel + pad stay 0
    n_sum = sums.shape[1] // LANE
    for g in range(n_sum):
        out_ref[pl.ds(n_lut + g, 1), :] = sums[:, g * LANE:(g + 1) * LANE]
    tail = out_ref.shape[0] - n_lut - n_sum
    if tail:
        out_ref[pl.ds(n_lut + n_sum, tail), :] = jnp.zeros(
            (tail, LANE), out_ref.dtype
        )


def _ext_lut_call(luts, caddr_t, *, t_pad, n_combos, shared, interpret):
    """luts (Q, M, 256); caddr_t (L, C) shared or (Q, L, C) per query, C a
    LANE multiple -> (Q, t_pad) flat tables."""
    q, m, ncodes = luts.shape
    n_lut = m * ncodes // LANE
    n_out = t_pad // LANE
    caddr_block = (None,) * (not shared) + caddr_t.shape[-2:]
    out = pl.pallas_call(
        functools.partial(_ext_lut_kernel, n_combos=n_combos),
        grid=(q,),
        in_specs=[
            pl.BlockSpec((None, n_lut, LANE), lambda qi: (qi, 0, 0)),
            pl.BlockSpec(
                caddr_block,
                (lambda qi: (0, 0)) if shared else (lambda qi: (qi, 0, 0)),
            ),
        ],
        out_specs=pl.BlockSpec((None, n_out, LANE), lambda qi: (qi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((q, n_out, LANE), luts.dtype),
        interpret=interpret,
    )(luts.reshape(q, n_lut, LANE), caddr_t)
    return out.reshape(q, t_pad)


def _combo_lanes(combo_addrs: jax.Array) -> jax.Array:
    """(..., n_combos, L) -> (..., L, C) int32, combos padded to C lanes."""
    n_combos = combo_addrs.shape[-2]
    pad = max(LANE, -(-n_combos // LANE) * LANE) - n_combos
    widths = [(0, 0)] * (combo_addrs.ndim - 2) + [(0, pad), (0, 0)]
    padded = jnp.pad(combo_addrs.astype(jnp.int32), widths)
    return jnp.swapaxes(padded, -1, -2)


def _check_width(t_pad: int, m: int, n_combos: int) -> None:
    if t_pad % LANE or t_pad < m * NCODES + n_combos + 1:
        raise ValueError(
            f"t_pad={t_pad} must be a multiple of {LANE} holding "
            f"{m * NCODES + n_combos + 1} entries"
        )


@functools.partial(jax.jit, static_argnames=("t_pad", "interpret"))
def ext_lut_pairs_kernel(
    luts: jax.Array,
    combo_addrs: jax.Array,
    *,
    t_pad: int,
    interpret: bool = False,
) -> jax.Array:
    """Per-pair combos variant: combo_addrs (Q, n_combos, L) -- each probed
    cluster brings its own mined combo set (paper mines per cluster)."""
    q, m, _ = luts.shape
    n_combos = combo_addrs.shape[1]
    _check_width(t_pad, m, n_combos)
    return _ext_lut_call(
        luts, _combo_lanes(combo_addrs), t_pad=t_pad, n_combos=n_combos,
        shared=False, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("t_pad", "interpret"))
def ext_lut_kernel(
    luts: jax.Array,
    combo_addrs: jax.Array,
    *,
    t_pad: int,
    interpret: bool = False,
) -> jax.Array:
    """Fused extended-table build.

    Args:
      luts: (Q, M, 256) tables from `ops.build_luts`.
      combo_addrs: (n_combos, L) int32 flat addresses (col*256 + code) of the
        items of each mined combo.
      t_pad: output width, a LANE multiple >= M*256 + n_combos + 1; the
        tail beyond the combo sums is the zero-sentinel region.

    Returns:
      (Q, t_pad) float32 flat tables.
    """
    q, m, _ = luts.shape
    n_combos = combo_addrs.shape[0]
    _check_width(t_pad, m, n_combos)
    return _ext_lut_call(
        luts, _combo_lanes(combo_addrs), t_pad=t_pad, n_combos=n_combos,
        shared=True, interpret=interpret,
    )
