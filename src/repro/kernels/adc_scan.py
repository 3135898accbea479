"""Pallas TPU kernel: ADC scan (IVFPQ distance calculation, paper stage (c)).

PIM -> TPU mapping:
  * the LUT is pinned whole in VMEM for the life of the scan (WRAM analogue);
  * encoded points stream HBM -> VMEM in (block_n, W) tiles -- the tile height
    is the "MRAM read size" knob of paper Fig. 9/15;
  * the WRAM random gather `LUT[e_m + 256*m]` becomes either
      - `path="gather"`: lane gathers inside one vreg (`_gather_dists`), or
      - `path="onehot"`: a multi-hot GEMM on the MXU (`_onehot_dists`).

Layout shared by every scan kernel in this package (what Mosaic accepts):
  * a flat table of A entries, zero-padded to a LANE multiple, is handed to
    the kernel as an (A / 128, 128) block of "chunks": entry `a` sits at
    chunk `a >> 7`, lane `a & 127`.  Addresses at or past A read 0, so the
    zero-sentinel slot of the §4.3 extended table needs no storage of its
    own;
  * codes are stored column-major, (W, N): a (W, block_n) tile holds the
    codes of one column along lanes, so a lane gather resolves 128 rows per
    vreg, the per-row sum over columns is a sublane reduction that leaves
    the (1, block_n) distances lane-dense, and the array stays lane-dense
    in HBM (a (N, W) array with W = 16 would be padded to 128 lanes).

The *flat* variant scans §4.3 direct-address codes against the extended
[LUT | combo-sums | 0] table; identical kernel structure, wider table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NCODES = 256
LANE = 128


def as_chunks(table: jax.Array) -> jax.Array:
    """(..., A) flat tables -> (..., ceil(A / LANE), LANE) chunk rows.

    The tail past A is zero-filled; a LANE-multiple A reshapes for free."""
    a = table.shape[-1]
    pad = -a % LANE
    if pad:
        table = jnp.pad(table, [(0, 0)] * (table.ndim - 1) + [(0, pad)])
    return table.reshape(table.shape[:-1] + ((a + pad) // LANE, LANE))


def _lane_gather(rows: jax.Array, lane: jax.Array) -> jax.Array:
    """rows (W, 128) x lane (W, G <= 128) -> rows[w, lane[w, g]]."""
    return jnp.take_along_axis(rows, lane, axis=1, mode="promise_in_bounds")


def _gather_dists(
    tab_ref, addr_t: jax.Array, *, segmented: bool
) -> jax.Array:
    """(C, 128) chunked table ref x (W, BN) int32 addresses -> (1, BN) f32.

    `segmented`: `addr_t` holds raw plain codes (0..255) and column i reads
    LUT segment i, i.e. chunks 2i and 2i + 1 -- two gathers per column.
    Otherwise `addr_t` holds flat addresses into the whole table and every
    chunk is gathered and selected by `addr >> 7`.
    """
    w, bn = addr_t.shape
    groups = [(g0, min(LANE, bn - g0)) for g0 in range(0, bn, LANE)]
    lanes = [addr_t[:, g0:g0 + gw] & (LANE - 1) for g0, gw in groups]
    if segmented:
        lo = tab_ref[pl.ds(0, w, stride=2), :]
        hi = tab_ref[pl.ds(1, w, stride=2), :]
        vals = [
            jnp.where(
                addr_t[:, g0:g0 + gw] < LANE,
                _lane_gather(lo, lane),
                _lane_gather(hi, lane),
            )
            for (g0, gw), lane in zip(groups, lanes)
        ]
    else:
        chunks = [addr_t[:, g0:g0 + gw] >> 7 for g0, gw in groups]

        def body(c, vals):
            row = jnp.broadcast_to(tab_ref[pl.ds(c, 1), :], (w, LANE))
            return tuple(
                jnp.where(ch == c, _lane_gather(row, lane), v)
                for ch, lane, v in zip(chunks, lanes, vals)
            )

        init = tuple(jnp.zeros(lane.shape, tab_ref.dtype) for lane in lanes)
        vals = jax.lax.fori_loop(0, tab_ref.shape[0], body, init)
    parts = [jnp.sum(v, axis=0, keepdims=True) for v in vals]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _onehot_dists(tab_ref, addr_t: jax.Array) -> jax.Array:
    """Multi-hot x table GEMM: turns the gather into MXU contractions.

    One (128, BN) multi-hot block per 128-entry table chunk (W compares),
    contracted against that chunk's row at full f32 precision.
    """
    w, bn = addr_t.shape

    def body(c, acc):
        iota = jax.lax.broadcasted_iota(jnp.int32, (LANE, bn), 0) + c * LANE
        mh = jnp.zeros((LANE, bn), jnp.float32)
        for i in range(w):  # static unroll: W is small (<= M)
            mh = mh + (iota == addr_t[i:i + 1, :]).astype(jnp.float32)
        return acc + jnp.dot(
            tab_ref[pl.ds(c, 1), :].astype(jnp.float32), mh,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    acc = jax.lax.fori_loop(
        0, tab_ref.shape[0], body, jnp.zeros((1, bn), jnp.float32)
    )
    return acc.astype(tab_ref.dtype)


def tile_dists(
    tab_ref, codes_t: jax.Array, *, path: str, add_offsets: bool
) -> jax.Array:
    """(W, BN) code tile -> (1, BN) ADC distances against a chunked table.

    `add_offsets`: the tile holds raw uint8 plain codes (column i indexes
    LUT segment i); otherwise it holds flat table addresses.
    """
    addr_t = codes_t.astype(jnp.int32)
    if path == "onehot":
        if add_offsets:
            addr_t = addr_t + NCODES * jax.lax.broadcasted_iota(
                jnp.int32, addr_t.shape, 0
            )
        return _onehot_dists(tab_ref, addr_t)
    return _gather_dists(tab_ref, addr_t, segmented=add_offsets)


def _adc_scan_kernel(table_ref, addr_ref, out_ref, *, path: str):
    out_ref[...] = tile_dists(
        table_ref, addr_ref[...], path=path, add_offsets=False
    )


@functools.partial(
    jax.jit, static_argnames=("block_n", "path", "interpret")
)
def adc_scan_kernel(
    table: jax.Array,
    addrs: jax.Array,
    *,
    block_n: int = 1024,
    path: str = "gather",
    interpret: bool = False,
) -> jax.Array:
    """Scan pre-offset flat addresses against a flat table.

    Args:
      table: (T,) float32 flat LUT ([LUT] or [LUT | combos | 0]).
      addrs: (N, W) int32 flat addresses, N % block_n == 0 (ops.py pads).

    Returns:
      (N,) float32 distances.
    """
    n, w = addrs.shape
    assert n % block_n == 0, f"N={n} not a multiple of block_n={block_n}"
    chunks = as_chunks(table)
    out = pl.pallas_call(
        functools.partial(_adc_scan_kernel, path=path),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec(chunks.shape, lambda i: (0, 0)),   # whole table
            pl.BlockSpec((w, block_n), lambda i: (0, i)),   # stream codes
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), table.dtype),
        interpret=interpret,
    )(chunks, addrs.T)
    return out.reshape(n)
