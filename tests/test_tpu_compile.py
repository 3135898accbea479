"""The main-path kernels compile for a TPU v5e at SIFT1B widths.

Interpret mode accepts constructs the chip's compiler (Mosaic) refuses, so
these tests compile -- without a chip -- for a described `v5e:2x2`
topology: the tiles scan with plain and with co-occurrence codes, the LUT
build, the extended-table kernel, the re-rank kernel and the whole
`sharded_search` step on one chip and on the 2x2 mesh.  Widths are the
paper's SIFT1B serving shape (`configs/memanns.py`: dim 128, M=16,
nprobe 64, k=10, batches of 1000 queries) at the TPU geometry row of
`configs/autotune_defaults.json` (block_n 1024, rerank_block 128).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.lut_build import ext_lut_pairs_kernel

Q, K, K_PRIME, DIM, M, DSUB = 1000, 10, 64, 128, 16, 8
BLOCK_N, RERANK_BLOCK, NPROBE = 1024, 128, 64
PAIRS = 65536            # pow2 pair bucket of 1000 queries x nprobe 64
TILES = 4 * PAIRS
CAP = 6 * 2**20          # code rows per chip at 4M rows, slots aligned
N_COMBOS, COMBO_LEN = 256, 3
COOC_TABLE = 4480        # 16*256 + 256 combos + sentinel, LANE-aligned


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """Compiles for a described chip cannot be read back without one: keep
    them out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _hlo(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _tiles_shapes(sharding, table_width, code_dtype):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (
        s((PAIRS, table_width), jnp.float32),
        s((M, CAP), code_dtype),
        s((TILES,), jnp.int32), s((TILES,), jnp.int32), s((TILES,), jnp.int32),
        s((PAIRS,), jnp.int32), s((PAIRS,), jnp.int32),
        s((PAIRS,), jnp.float32), s((Q,), jnp.float32),
    )


@pytest.mark.parametrize(
    "table_width,code_dtype,add_offsets",
    [(M * 256, jnp.uint8, True), (COOC_TABLE, jnp.uint16, False)],
    ids=["plain", "cooc"],
)
def test_tiles_scan_compiles(
    one_chip, no_compile_cache, table_width, code_dtype, add_offsets
):
    def scan(tables, codes_t, tp, tb, tr, nv, pq, lb, bound):
        return ops.adc_topk_tiles(
            tables, codes_t, tp, tb, tr, nv, K, block_n=BLOCK_N,
            add_offsets=add_offsets, interpret=False, pair_q=pq,
            pair_lb=lb, bound=bound, n_queries=Q, with_stats=True,
        )

    hlo = _hlo(scan, *_tiles_shapes(one_chip, table_width, code_dtype))
    assert "tpu_custom_call" in hlo


def test_build_luts_compiles(one_chip, no_compile_cache):
    hlo = _hlo(
        ops.build_luts,
        jax.ShapeDtypeStruct((M, 256, DSUB), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((PAIRS, M, DSUB), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" not in hlo  # one fused XLA reduction


def test_ext_lut_pairs_kernel_compiles(one_chip, no_compile_cache):
    def ext(luts, combos):
        return ext_lut_pairs_kernel(
            luts, combos, t_pad=COOC_TABLE, interpret=False
        )

    hlo = _hlo(
        ext,
        jax.ShapeDtypeStruct((PAIRS, M, 256), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct(
            (PAIRS, N_COMBOS, COMBO_LEN), jnp.int32, sharding=one_chip
        ),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rerank_dists_compiles(one_chip, no_compile_cache, dtype):
    def rerank(queries, cand):
        return ops.rerank_dists(
            queries, cand, block_k=RERANK_BLOCK, interpret=False
        )

    hlo = _hlo(
        rerank,
        jax.ShapeDtypeStruct((Q, DIM), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((Q, K_PRIME, DIM), dtype, sharding=one_chip),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n_chips", [1, 4], ids=["one_chip", "v5e_2x2"])
def test_sharded_search_step_compiles(topo, no_compile_cache, n_chips):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.retrieval.search import DPU_AXIS, sharded_search

    mesh = Mesh(np.asarray(topo.devices[:n_chips]), (DPU_AXIS,))
    dev = NamedSharding(mesh, PartitionSpec(DPU_AXIS))
    rep = NamedSharding(mesh, PartitionSpec())
    pairs, tiles, slots = PAIRS // n_chips, TILES // n_chips, 4160

    def s(shape, dtype, sharding=dev):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    shapes = (
        s((n_chips, M, CAP // n_chips), jnp.uint16),          # codes_t
        s((n_chips, CAP // n_chips), jnp.int32),              # vec_ids
        s((n_chips, slots), jnp.int32), s((n_chips, slots), jnp.int32),
        s((n_chips, slots, N_COMBOS, COMBO_LEN), jnp.int32),  # combos
        s((M, 256, DSUB), jnp.float32, rep),                  # codebook
        s((n_chips, pairs, DIM), jnp.float32),                # residuals
        s((n_chips, pairs), jnp.int32), s((n_chips, pairs), jnp.int32),
        s((n_chips, pairs), jnp.bool_),
        s((n_chips, Q, NPROBE), jnp.int32),                   # query_pairs
        s((n_chips, tiles), jnp.int32), s((n_chips, tiles), jnp.int32),
        s((n_chips, tiles), jnp.int32),
        s((n_chips, pairs), jnp.float32), s((Q,), jnp.float32, rep),
    )

    def step(*args):
        return sharded_search(
            *args, mesh=mesh, n_queries=Q, k=K, block_n=BLOCK_N,
            window=16 * BLOCK_N, path="gather", add_offsets=False,
            scan="tiles", interpret=False,
        )

    hlo = _hlo(step, *shapes)
    assert "tpu_custom_call" in hlo
    if n_chips > 1:
        assert "all-gather" in hlo  # the cross-chip merge
