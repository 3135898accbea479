#!/usr/bin/env python3
"""Smoke run of the served IVF-PQ search path on TPU chips.

    python3 chip_smoke.py             # one chip: the `pq` and `exact` phases
    python3 chip_smoke.py --chips 4   # `pq` on a four-chip mesh + on one chip

Builds a corpus at the paper's SIFT1B widths (`repro.configs.memanns.SIFT1B`:
dim 128, M=16, IVF 4096, nprobe 64, k=10, batches of 1000 queries) from
`make_clustered_vectors` with a fixed seed, through `MemANNSEngine.build`
(k-means and PQ trained on a 160k-row sample), and serves it through
`ServingEngine` -- warmup, then micro-batches of 1000 queries -- with the
serving defaults of `repro.launch.serve`: tiles scan, gather path, early
pruning, co-occurrence encoding.  Kernel geometry is the TPU row of
`configs/autotune_defaults.json` (block_n 1024, rerank_block 128).

Phases (any failure fails the run):
  pq     rerank off.  On 100 queries the served ids agree with the flat jnp
         reference `repro.core.index.search` (>= 99% of ids) and the
         distances match it to 1e-4 relative.
  exact  rerank exact against the raw vectors on the device.  Returned
         distances match a numpy f32 recomputation; recall@10 against
         exact brute force on 100 queries is printed.
  Both   the steady state records 0 compiles after warmup, and the compiled
         search step holds the scan kernel as a `tpu_custom_call` (no
         interpret mode).
With --chips 4 the corpus is built on a four-device mesh and only `pq`
runs, plus the same phase on one chip; the two must agree under the pq
rule.

Lines before the last are smoke facts, not benchmark metrics.  The last
line is the JSON result.  Without a TPU the script exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# 4M rows, not 10M: the host-side build (co-occurrence mining and
# re-encoding of every cluster in numpy) grows with the corpus, and the
# four-chip run builds the shards twice (four-device and one-device mesh)
# inside the same time limit
ROWS = 4_000_000
TRAIN_ROWS = 160_000       # k-means + PQ training sample (~39 per centroid)
HISTORY_QUERIES = 2_000    # query log for Algorithm 1's frequency estimate
BATCHES = 3                # micro-batches served per phase
CHECK_QUERIES = 100        # queries compared against the references
SEED = 0
RERANK_BLOCK = 128         # TPU row of configs/autotune_defaults.json


def fact(name: str, **values) -> None:
    """One smoke fact (not a benchmark metric) on its own line."""
    print(f"smoke fact {name}: {json.dumps(values, sort_keys=True)}",
          flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


@dataclasses.dataclass
class Workload:
    cfg: object               # RetrievalConfig (widths)
    rows: int
    xs: object                # (rows, dim) f32 corpus
    history: object           # (H, dim) f32 query log
    queries: object           # (batches * batch_queries, dim) f32


def make_workload(rows: int = ROWS, batches: int = BATCHES,
                  cfg=None) -> Workload:
    from repro.configs.memanns import SIFT1B
    from repro.data import SkewedVectorDataset, make_clustered_vectors

    cfg = cfg or SIFT1B
    t0 = time.perf_counter()
    xs, centers, _ = make_clustered_vectors(
        rows, cfg.dim, cfg.n_clusters, seed=SEED, pattern_pool=64
    )
    stream = SkewedVectorDataset(centers, seed=SEED)
    wl = Workload(
        cfg=cfg, rows=rows, xs=xs,
        history=stream.queries(HISTORY_QUERIES, seed=1),
        queries=stream.queries(batches * cfg.batch_queries, seed=2),
    )
    fact("data", rows=rows, dim=cfg.dim,
         seconds=time.perf_counter() - t0)
    return wl


def build_engine(wl: Workload, n_devices: int, train_rows: int = TRAIN_ROWS):
    import jax

    from repro.launch.mesh import make_retrieval_mesh
    from repro.retrieval import MemANNSEngine

    cfg = wl.cfg
    t0 = time.perf_counter()
    eng = MemANNSEngine.build(
        jax.random.PRNGKey(SEED), wl.xs, cfg.n_clusters, cfg.m,
        mesh=make_retrieval_mesh(n_devices),
        history_queries=wl.history, nprobe_history=cfg.nprobe,
        use_cooc=True, n_combos=cfg.n_combos, block_n=cfg.block_n,
        train_subsample=train_rows, rerank_block=RERANK_BLOCK,
        store_raw=n_devices == 1,
    )
    sizes = eng.index.cluster_sizes()
    fact("build", devices=n_devices, seconds=time.perf_counter() - t0,
         clusters=int(sizes.size), max_cluster_rows=int(sizes.max()),
         code_bytes_per_device=int(eng.shards.bytes_per_device()),
         code_width=int(eng.shards.width))
    return eng


def serve_phase(name: str, eng, wl: Workload):
    """Warm up, serve every query in micro-batches, check the steady
    state; returns (dists, ids) of all queries."""
    import jax

    from repro.retrieval import ServingEngine

    cfg = wl.cfg
    srv = ServingEngine(
        eng, nprobe=cfg.nprobe, k=cfg.k, micro_batch=cfg.batch_queries,
        autotune="off",
    )
    t0 = time.perf_counter()
    buckets = srv.warmup()
    warm_s = time.perf_counter() - t0
    compiles = srv.stats.compiles
    t0 = time.perf_counter()
    dists, ids = srv.search(wl.queries)
    serve_s = time.perf_counter() - t0
    check(srv.stats.compiles == compiles,
          f"{name}: {srv.stats.compiles - compiles} steady-state compiles")
    check(dists.shape == (len(wl.queries), cfg.k), f"{name}: output shape")
    fact(f"{name} serve", devices=len(eng.mesh.devices.flat),
         warmup_seconds=warm_s, warmed_pair_buckets=buckets,
         steady_state_compiles=srv.stats.compiles - compiles,
         micro_batches=len(wl.queries) // cfg.batch_queries,
         batch_queries=cfg.batch_queries, serve_seconds=serve_s,
         device_memory=_memory_facts(jax.devices()[0]))

    # the compiled step holds the scan kernel for the chip: a Pallas kernel
    # left in interpret mode would appear as plain XLA ops instead
    plan = eng.plan_batch(
        wl.queries[:cfg.batch_queries], cfg.nprobe,
        capacity_floor=srv.capacity_floor, query_width=cfg.nprobe,
    )
    k_scan = eng.k_prime(cfg.k) if eng.rerank == "exact" else cfg.k
    hlo = eng.compiled_search_text(plan, k_scan)
    kernel = [
        ln for ln in hlo.splitlines()
        if "tpu_custom_call" in ln and "adc_topk_tiles_kernel" in ln
    ]
    check(bool(kernel), f"{name}: no tpu_custom_call for the scan kernel")
    fact(f"{name} kernel", tpu_custom_calls=hlo.count("tpu_custom_call"))
    return dists, ids


def _memory_facts(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                        "bytes_limit") if k in stats}


def agreement(ids_a, ids_b) -> float:
    """Share of ids of `ids_a` found in the same query's row of `ids_b`."""
    hits = sum(len(set(a.tolist()) & set(b.tolist()))
               for a, b in zip(ids_a, ids_b))
    return hits / ids_a.size


def check_against(name: str, d, i, d_ref, i_ref, ref: str) -> None:
    import numpy as np

    agree = agreement(i, i_ref)
    fin = np.isfinite(d_ref)
    check(bool((np.isfinite(d) == fin).all()),
          f"{name}: result slots filled differently from {ref}")
    rel = float(np.max(np.abs(d[fin] - d_ref[fin])
                       / np.maximum(np.abs(d_ref[fin]), 1e-30), initial=0.0))
    fact(f"{name} vs {ref}", queries=len(i), id_agreement=agree,
         max_rel_dist_diff=rel)
    check(agree >= 0.99, f"{name}: id agreement {agree} with {ref} < 0.99")
    check(rel <= 1e-4, f"{name}: distances differ from {ref} by {rel}")


def pq_phase(eng, wl: Workload):
    import numpy as np

    from repro.core.index import search as flat_search

    dists, ids = serve_phase("pq", eng, wl)
    q = wl.queries[:CHECK_QUERIES]
    t0 = time.perf_counter()
    d_ref, i_ref = flat_search(eng.index, q, wl.cfg.nprobe, wl.cfg.k)
    fact("pq reference", seconds=time.perf_counter() - t0)
    check(bool(np.isfinite(dists).all()), "pq: non-finite distances")
    check_against("pq", dists[:CHECK_QUERIES], ids[:CHECK_QUERIES],
                  d_ref, i_ref, "flat reference")
    return dists, ids


def exact_topk(xs, queries, k: int, chunk: int = 1 << 20):
    """Exact top-k ids by brute force on the device, in row chunks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def part(x, q):
        qx = jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST)
        d = jnp.sum(q * q, 1)[:, None] - 2.0 * qx + jnp.sum(x * x, 1)[None, :]
        neg, idx = jax.lax.top_k(-d, k)
        return -neg, idx

    q = jnp.asarray(queries, jnp.float32)
    best_d = np.full((len(queries), 0), np.inf, np.float32)
    best_i = np.zeros((len(queries), 0), np.int64)
    for s in range(0, len(xs), chunk):
        x = xs[s:s + chunk]
        if len(x) < chunk:  # one shape for every chunk
            x = np.concatenate([x, np.full((chunk - len(x), x.shape[1]),
                                           1e9, np.float32)])
        d, i = part(jnp.asarray(x), q)
        best_d = np.concatenate([best_d, np.asarray(d)], axis=1)
        best_i = np.concatenate([best_i, np.asarray(i) + s], axis=1)
        sel = np.argsort(best_d, axis=1, kind="stable")[:, :k]
        best_d = np.take_along_axis(best_d, sel, 1)
        best_i = np.take_along_axis(best_i, sel, 1)
    return best_i


def exact_phase(eng, wl: Workload) -> None:
    import numpy as np

    dists, ids = serve_phase(
        "exact", dataclasses.replace(eng, rerank="exact"), wl
    )
    check(bool((ids >= 0).all()), "exact: missing results")
    x = wl.xs[ids]                                   # (Q, k, dim)
    d_np = np.sum((x - wl.queries[:, None, :]) ** 2, axis=-1,
                  dtype=np.float32)
    rel = float(np.max(np.abs(dists - d_np) / np.maximum(d_np, 1e-30)))
    fact("exact vs numpy f32 distances", queries=len(ids),
         max_rel_dist_diff=rel)
    check(rel <= 1e-5, f"exact: distances differ from numpy by {rel}")
    t0 = time.perf_counter()
    truth = exact_topk(wl.xs, wl.queries[:CHECK_QUERIES], wl.cfg.k)
    fact("exact recall", queries=CHECK_QUERIES, k=wl.cfg.k,
         recall_at_k=agreement(truth, ids[:CHECK_QUERIES]),
         brute_force_seconds=time.perf_counter() - t0)


def on_devices(eng, n_devices: int):
    """The same trained index, placed and packed for an n-device mesh
    (what `MemANNSEngine.build` does after training)."""
    import numpy as np

    from repro.core.placement import place_clusters
    from repro.launch.mesh import make_retrieval_mesh
    from repro.retrieval.layout import build_shards

    t0 = time.perf_counter()
    placement = place_clusters(
        eng.index.cluster_sizes().astype(np.float64), eng.freqs, n_devices,
        centroids=eng.index.centroids,
    )
    shards = build_shards(
        eng.index, placement, use_cooc=True, n_combos=eng.shards.n_combos,
        block_n=eng.shards.block_n,
    )
    fact("re-place", devices=n_devices, seconds=time.perf_counter() - t0)
    return dataclasses.replace(
        eng, placement=placement, shards=shards,
        mesh=make_retrieval_mesh(n_devices), raw=None, _dev_arrays=None,
        _raw_arrays=None,
    )


def four_chip_phase(wl: Workload) -> None:
    """`pq` on a four-device mesh (checked against the flat reference),
    compared with the same serving run on one device."""
    eng4 = build_engine(wl, 4)
    d4, i4 = pq_phase(eng4, wl)
    d1, i1 = serve_phase("pq one chip", on_devices(eng4, 1), wl)
    check_against("pq four chips", d4, i4, d1, i1, "one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.env import setup_env
    except ImportError:
        print("chip_smoke: no src/repro next to this script; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    setup_env(platform="tpu")  # a TPU that fails to start is an error

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    fact("device", platform=devices[0].platform,
         kind=devices[0].device_kind, count=len(devices))

    wl = make_workload()
    if args.chips == 4:
        four_chip_phase(wl)
    else:
        eng = build_engine(wl, 1)
        pq_phase(eng, wl)
        exact_phase(eng, wl)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
