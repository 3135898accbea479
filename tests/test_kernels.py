"""Per-kernel allclose sweeps against the pure-jnp oracles in kernels/ref.py.

Every Pallas kernel runs in interpret mode (CPU container; TPU is the lower
target) across shape/dtype/path sweeps.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


def _lut(m, dtype=np.float32):
    return jnp.asarray(RNG.normal(0, 1, (m, 256)).astype(dtype))


def _codes(n, m):
    return jnp.asarray(RNG.integers(0, 256, (n, m)).astype(np.uint8))


@pytest.mark.parametrize("m", [8, 16, 20])
@pytest.mark.parametrize("n", [100, 1024, 2500])
@pytest.mark.parametrize("path", ["gather", "onehot"])
def test_adc_scan_sweep(m, n, path):
    lut, codes = _lut(m), _codes(n, m)
    got = ops.adc_scan(lut, codes, block_n=256, path=path)
    want = ref.adc_scan_ref(lut, codes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block_n", [128, 512, 1024])
def test_adc_scan_block_sizes(block_n):
    lut, codes = _lut(16), _codes(3000, 16)
    got = ops.adc_scan(lut, codes, block_n=block_n)
    np.testing.assert_allclose(got, ref.adc_scan_ref(lut, codes), rtol=1e-5)


@pytest.mark.parametrize("w", [4, 12, 16])
def test_adc_scan_flat(w):
    a = 16 * 256 + 33
    ext = jnp.asarray(RNG.normal(0, 1, (a,)).astype(np.float32))
    addrs = jnp.asarray(RNG.integers(0, a, (1500, w)).astype(np.int32))
    got = ops.adc_scan_flat(ext, addrs, block_n=256)
    np.testing.assert_allclose(
        got, ref.adc_scan_flat_ref(ext, addrs), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("q", [1, 4])
@pytest.mark.parametrize("k", [1, 10, 50])
def test_adc_topk(q, k):
    m = 16
    luts = jnp.stack([_lut(m) for _ in range(q)])
    codes = _codes(2200, m)
    tv, ti = ops.adc_topk(luts, codes, k, block_n=512)
    rv, ri = ref.adc_topk_ref(luts, codes, k)
    np.testing.assert_allclose(tv, rv, rtol=1e-5, atol=1e-5)
    assert jnp.all(ti == ri)


def test_adc_topk_flat():
    q, k, m, n_combos = 3, 10, 8, 17
    a = m * 256 + n_combos + 1
    ext = jnp.asarray(RNG.normal(0, 1, (q, a)).astype(np.float32))
    addrs = jnp.asarray(RNG.integers(0, a - 1, (900, 6)).astype(np.int32))
    tv, ti = ops.adc_topk_flat(ext, addrs, k, block_n=256)
    rv, ri = ref.adc_topk_flat_ref(ext, addrs, k)
    np.testing.assert_allclose(tv, rv, rtol=1e-5, atol=1e-5)
    assert jnp.all(ti == ri)


def test_adc_topk_pairs():
    p, l, w, k, m = 5, 1024, 8, 7, 8
    tables = jnp.asarray(RNG.normal(0, 1, (p, m * 256 + 9)).astype(np.float32))
    addrs = jnp.asarray(RNG.integers(0, m * 256, (p, l, w)).astype(np.int32))
    n_valid = jnp.asarray(RNG.integers(1, l, (p,)).astype(np.int32))
    tv, ti = ops.adc_topk_pairs(tables, addrs, n_valid, k, block_n=256)
    for i in range(p):
        d = ref.adc_scan_flat_ref(tables[i], addrs[i])
        d = jnp.where(jnp.arange(l) < n_valid[i], d, jnp.inf)
        rv, ri = jax.lax.top_k(-d, k)
        np.testing.assert_allclose(tv[i], -rv, rtol=1e-5, atol=1e-5)
        assert jnp.all(ti[i] == ri)


def test_adc_topk_windows():
    """Scalar-prefetch windowed kernel == per-pair oracle."""
    bn, k, m = 256, 9, 8
    cap, w, p = 4096, 8, 6
    window = 1024
    codes = jnp.asarray(RNG.integers(0, m * 256, (cap, w)).astype(np.int32))
    tables = jnp.asarray(RNG.normal(0, 1, (p, m * 256 + 9)).astype(np.float32))
    starts = jnp.asarray((RNG.integers(0, (cap - window) // bn, p) * bn).astype(np.int32))
    n_valid = jnp.asarray(RNG.integers(1, window, (p,)).astype(np.int32))
    tv, ti = ops.adc_topk_windows(
        tables, codes.T, starts, n_valid, k, window=window, block_n=bn
    )
    for i in range(p):
        win = codes[starts[i] : starts[i] + window]
        d = ref.adc_scan_flat_ref(tables[i], win)
        d = jnp.where(jnp.arange(window) < n_valid[i], d, jnp.inf)
        rv, ri = jax.lax.top_k(-d, k)
        np.testing.assert_allclose(tv[i], -rv, rtol=1e-5, atol=1e-5)
        assert jnp.all(ti[i] == ri)


@pytest.mark.parametrize("dtype", ["uint8", "uint16"])
def test_adc_topk_windows_compact_dtypes(dtype):
    """Compact HBM storage: uint8 raw codes (offsets added in VMEM) and
    uint16 direct addresses match the int32 oracle."""
    from repro.kernels.adc_topk import adc_topk_windows_kernel

    bn, k, m, cap, p, window = 128, 5, 8, 2048, 4, 512
    add_offsets = dtype == "uint8"
    hi = 256 if add_offsets else m * 256
    codes = jnp.asarray(RNG.integers(0, hi, (cap, m)).astype(dtype))
    tables = jnp.asarray(
        RNG.normal(0, 1, (p, m * 256 + 1)).astype(np.float32)
    )
    sizes = jnp.asarray(RNG.integers(1, window, (p,)).astype(np.int32))
    starts = jnp.asarray((np.arange(p) * 3 * bn).astype(np.int32))
    tv, ti, _ = adc_topk_windows_kernel(
        tables, codes.T, starts // bn, sizes, k=k, window=window,
        block_n=bn, add_offsets=add_offsets, interpret=True,
    )
    for i in range(p):
        win = codes[starts[i] : starts[i] + window].astype(jnp.int32)
        if add_offsets:
            win = win + (jnp.arange(m) * 256)[None, :]
        d = ref.adc_scan_flat_ref(tables[i], win)
        d = jnp.where(jnp.arange(window) < sizes[i], d, jnp.inf)
        rv, ri = jax.lax.top_k(-d, k)
        rv = -rv
        fin = np.isfinite(np.asarray(rv))
        np.testing.assert_allclose(
            np.asarray(tv[i])[fin], np.asarray(rv)[fin], rtol=1e-5
        )
        assert np.all(np.asarray(ti[i])[fin] == np.asarray(ri)[fin])


def test_adc_topk_tiles():
    """Tile-list work queue == per-pair oracle (the padded-DMA-free path)."""
    from repro.kernels.adc_topk import adc_topk_tiles_kernel

    bn, k, m, cap, p = 128, 7, 8, 2048, 5
    codes = jnp.asarray(RNG.integers(0, 256, (cap, m)).astype(np.uint8))
    tables = jnp.asarray(RNG.normal(0, 1, (p, m * 256 + 1)).astype(np.float32))
    sizes = RNG.integers(1, 512, p).astype(np.int32)
    starts = (np.arange(p) * 3 * bn).astype(np.int32)
    tp_, tb_, tr_ = [], [], []
    for i in range(p):
        for b in range(-(-int(sizes[i]) // bn)):
            tp_.append(i)
            tb_.append(starts[i] // bn + b)
            tr_.append(b * bn)
    tp_ += [p, p]  # dummy padding tiles
    tb_ += [0, 0]
    tr_ += [0, 0]
    tv, ti, _ = adc_topk_tiles_kernel(
        tables, codes.T, jnp.asarray(tp_), jnp.asarray(tb_), jnp.asarray(tr_),
        jnp.asarray(sizes), k=k, block_n=bn, add_offsets=True, interpret=True,
    )
    for i in range(p):
        win = codes[starts[i] : starts[i] + 512].astype(jnp.int32) + (
            jnp.arange(m) * 256
        )[None, :]
        d = ref.adc_scan_flat_ref(tables[i], win)
        d = jnp.where(jnp.arange(512) < sizes[i], d, jnp.inf)
        rv, ri = jax.lax.top_k(-d, k)
        rv = -rv
        fin = np.isfinite(np.asarray(rv))
        np.testing.assert_allclose(
            np.asarray(tv[i])[fin], np.asarray(rv)[fin], rtol=1e-5
        )
        assert np.all(np.asarray(ti[i])[fin] == np.asarray(ri)[fin])


@pytest.mark.parametrize("dsub", [4, 8])
@pytest.mark.parametrize("q", [1, 5])
def test_lut_build(dsub, q):
    m = 16
    cb = jnp.asarray(RNG.normal(0, 1, (m, 256, dsub)).astype(np.float32))
    qmc = jnp.asarray(RNG.normal(0, 1, (q, m, dsub)).astype(np.float32))
    got = ops.build_luts(cb, qmc)
    np.testing.assert_allclose(
        got, ref.lut_build_ref(cb, qmc), rtol=1e-4, atol=1e-4
    )


def test_ext_lut_build():
    q, m, nc = 4, 8, 12
    luts = jnp.asarray(RNG.normal(0, 1, (q, m, 256)).astype(np.float32))
    cols = jnp.asarray(RNG.integers(0, m, (nc, 3)).astype(np.int32))
    codes = jnp.asarray(RNG.integers(0, 256, (nc, 3)).astype(np.int32))
    got = ops.build_ext_luts(luts, cols, codes)
    want = ref.ext_lut_build_ref(luts, cols, codes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_early_pruning_does_not_change_results():
    """§4.4 pruning is a pure optimization: sorted-ascending inputs (worst
    case for pruning) and shuffled inputs give identical top-k."""
    m, k = 8, 10
    lut = _lut(m)
    codes_sorted = _codes(2048, m)
    d = np.asarray(ref.adc_scan_ref(lut, codes_sorted))
    order = np.argsort(-d)  # descending: every tile improves -> no pruning
    codes_desc = jnp.asarray(np.asarray(codes_sorted)[order])
    order2 = np.argsort(d)  # ascending: all later tiles pruned
    codes_asc = jnp.asarray(np.asarray(codes_sorted)[order2])
    for codes in (codes_desc, codes_asc):
        tv, ti = ops.adc_topk(lut[None], codes, k, block_n=256)
        rv, ri = ref.adc_topk_ref(lut[None], codes, k)
        np.testing.assert_allclose(tv, rv, rtol=1e-5, atol=1e-5)
        assert jnp.all(ti == ri)
