"""Scan-work accounting: the tiles scan's grid steps by what they serve.

`MemANNSEngine.plan_scan_steps` splits the scan kernel's grid steps for a
plan into `query` (tiles of real queries), `pad` (tiles of a padded
micro-batch's padding rows) and `bucket` (capacity dummies).  Pinned here:

  * the three add up to the grid the kernel is traced with (read off the
    step's jaxpr), on both scan paths and with the queue padded to whole
    chunks; on the tiles path `query + pad` is `plan_tile_count`;
  * a full micro-batch has no padding steps, a one-query call has some;
  * `upanns_scan_steps_total` adds exactly the collected batches' counts,
    in the engine's registry and in the process-lifetime one (nothing
    with metrics off, nothing at warm-up);
  * `upanns_scan_rows_merged_total` adds exactly the kernels' rows-merged
    lane of the collected batches, per device, in both registries;
  * `Tracer(profiler=True)` hands the `dispatch` span's step counts to
    its `jax.profiler.TraceAnnotation`;
  * the search and re-rank steps carry their named scopes.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.adc_topk import TILE_CHUNK
from repro.obs.metrics import PROCESS_REGISTRY
from repro.obs.trace import Tracer
from repro.retrieval import MemANNSEngine, ServingEngine
from repro.retrieval.engine import make_dpu_mesh
from repro.retrieval.search import sharded_rerank, sharded_search
from repro.retrieval.serving import SCAN_STEP_KINDS

NPROBE = 8
K = 10
MB = 16
STEPS = "upanns_scan_steps_total"
MERGED = "upanns_scan_rows_merged_total"


@pytest.fixture(scope="module")
def engine(clustered_data):
    xs, _, _, hist = clustered_data
    return MemANNSEngine.build(
        jax.random.PRNGKey(0), xs, n_clusters=32, m=8,
        history_queries=hist, use_cooc=False, n_combos=32,
        block_n=256, kmeans_iters=8, pq_iters=6,
    )


def _padded(qs, q_real):
    """A micro-batch of `q_real` queries padded as serving pads it."""
    return np.concatenate(
        [qs[:q_real], np.broadcast_to(qs[:1], (MB - q_real, qs.shape[1]))]
    )


def _kernel_grid_steps(eng, plan):
    """Grid steps of the step's scan kernel, read off its jaxpr: each
    pallas_call's grid size times the trip counts of the loops around it,
    times the devices of the shard_map.  (No co-occurrence tables in this
    engine, so the scan is the step's only Pallas kernel.)"""
    args, static, _ = eng._step_inputs(plan, K)
    closed = jax.make_jaxpr(functools.partial(sharded_search, **static))(
        *args
    )
    found = []

    def walk(jaxpr, mult):
        for eqn in jaxpr.eqns:
            m = mult * eqn.params["length"] if eqn.primitive.name == "scan" \
                else mult
            if eqn.primitive.name == "pallas_call":
                found.append(m * int(np.prod(eqn.params["grid_mapping"].grid)))
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr, m)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub, m)

    walk(closed.jaxpr, 1)
    assert len(found) == 1, found
    return found[0] * eng.shards.ndev


def _loop_classes(plan, q_real):
    """(query, pad) tiles of a tiles plan, classed one tile at a time."""
    query = pad = 0
    p = plan.pairs_per_dev
    for d in range(plan.tile_pair.shape[0]):
        for pair in plan.tile_pair[d]:
            if pair == p:
                continue
            if plan.pair_q[d, pair] < q_real:
                query += 1
            else:
                pad += 1
    return query, pad


@pytest.mark.parametrize(
    "scan,tiles_per_dev",
    [("tiles", None), ("tiles", TILE_CHUNK + 512), ("windows", None)],
    ids=["tiles", "tiles-chunk-padded", "windows"],
)
@pytest.mark.parametrize("q_real", [MB, 5, 1])
def test_steps_add_up_to_the_kernel_grid(
    engine, clustered_data, scan, tiles_per_dev, q_real
):
    qs = clustered_data[2]
    eng = dataclasses.replace(engine, scan=scan)
    plan = eng.plan_batch(
        _padded(qs, q_real), NPROBE, tiles_per_dev=tiles_per_dev
    )
    query, pad, bucket = eng.plan_scan_steps(plan, q_real)
    assert min(query, pad, bucket) >= 0
    assert query + pad + bucket == _kernel_grid_steps(eng, plan)
    assert query > 0
    if q_real == MB:
        assert pad == 0
    else:
        assert pad > 0
    if scan == "tiles":
        assert query + pad == eng.plan_tile_count(plan)
        assert (query, pad) == _loop_classes(plan, q_real)
        if tiles_per_dev is not None:  # the queue padded to whole chunks
            assert bucket >= eng.shards.ndev * (2 * TILE_CHUNK - tiles_per_dev)
    else:
        per_pair = eng.shards.window // eng.shards.block_n
        assert bucket == int((~plan.pair_valid).sum()) * per_pair


def _counter(stats):
    return {k: stats.m_scan_steps.get(kind=k) for k in SCAN_STEP_KINDS}


def _process_counter():
    fam = PROCESS_REGISTRY.families()[STEPS]
    return {k: fam.get(kind=k) for k in SCAN_STEP_KINDS}


def _merged(fam) -> dict:
    """{device: rows merged} of a rows-merged counter family."""
    return {} if fam is None else {
        key[0]: s.value for key, s in fam.series.items()}


def _process_merged() -> dict:
    return _merged(PROCESS_REGISTRY.families().get(MERGED))


@pytest.mark.parametrize("scan", ["tiles", "windows"])
def test_rows_merged_counter_is_the_kernel_lane(engine, clustered_data, scan):
    """The rows-merged counter's delta, per device, equals the kernels'
    third stats lane summed over the collected batches."""
    qs = clustered_data[2]
    eng = dataclasses.replace(engine, scan=scan)
    srv = ServingEngine(eng, nprobe=NPROBE, k=K, micro_batch=MB,
                        pipeline_depth=1)
    srv.warmup()
    lanes = []
    orig = srv._collect_micro_batch

    def spy(handle, q_n, *a, **kw):
        lanes.append(np.asarray(handle.prune_stats)[:, 2])
        return orig(handle, q_n, *a, **kw)

    srv._collect_micro_batch = spy
    before = _merged(srv.stats.registry.families()[MERGED])
    p_before = _process_merged()
    for n in (1, 16, 24, 7):
        srv.search(qs[:n])
    want = np.sum(lanes, axis=0)
    assert len(lanes) == 5 and want.sum() > 0
    after = _merged(srv.stats.registry.families()[MERGED])
    p_after = _process_merged()
    for dev, n in enumerate(want):
        assert after[str(dev)] - before.get(str(dev), 0.0) == n
        assert p_after[str(dev)] - p_before.get(str(dev), 0.0) == n


@pytest.mark.parametrize("scan", ["tiles", "windows"])
def test_counter_delta_is_the_collected_batches(engine, clustered_data, scan):
    """The counter's delta over a ragged stream equals the sum of the
    collected batches' plan counts, and a one-query call pads."""
    qs = clustered_data[2]
    eng = dataclasses.replace(engine, scan=scan)
    srv = ServingEngine(eng, nprobe=NPROBE, k=K, micro_batch=MB,
                        pipeline_depth=1)
    srv.warmup()
    collected = []
    orig = srv._collect_micro_batch

    def spy(handle, q_n, *a, **kw):
        collected.append((handle.plan, q_n))
        return orig(handle, q_n, *a, **kw)

    srv._collect_micro_batch = spy
    before, p_before = _counter(srv.stats), _process_counter()
    for n in (1, 16, 24, 7):
        srv.search(qs[:n])
    after, p_after = _counter(srv.stats), _process_counter()
    want = np.sum([eng.plan_scan_steps(p, q) for p, q in collected], axis=0)
    assert [after[k] - before[k] for k in SCAN_STEP_KINDS] == list(want)
    # the process-lifetime twin adds the same
    assert [p_after[k] - p_before[k] for k in SCAN_STEP_KINDS] == list(want)
    assert len(collected) == 5
    one = eng.plan_scan_steps(*collected[0])
    assert collected[0][1] == 1 and one[1] > 0
    snap = srv.stats.snapshot()[STEPS]
    assert snap["type"] == "counter" and snap["labels"] == ["kind"]


def test_metrics_off_counts_no_process_steps(engine, clustered_data):
    """With metrics off neither registry counts, and warm-up (which
    collects no micro-batch) adds nothing to the process registry."""
    qs = clustered_data[2]
    before, merged = _process_counter(), _process_merged()
    on = ServingEngine(engine, nprobe=NPROBE, k=K, micro_batch=MB)
    on.warmup()
    assert _process_counter() == before
    assert _process_merged() == merged
    off = ServingEngine(engine, nprobe=NPROBE, k=K, micro_batch=MB,
                        metrics=False)
    off.warmup()
    off.search(qs[:24])
    assert _process_counter() == before
    assert _process_merged() == merged
    assert off.stats.snapshot() == {}


def test_dispatch_annotation_carries_the_steps(
    engine, clustered_data, monkeypatch
):
    qs = clustered_data[2]
    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    srv = ServingEngine(engine, nprobe=NPROBE, k=K, micro_batch=MB,
                        tracer=Tracer(profiler=True))
    srv.warmup()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    srv.search(qs[:3])
    dispatch = [kw for name, kw in seen if name == "dispatch"]
    assert len(dispatch) == 1, seen
    kw = dispatch[0]
    steps = (kw["steps_query"], kw["steps_pad"], kw["steps_bucket"])
    assert steps == tuple(int(srv.stats.m_scan_steps.get(kind=k))
                          for k in SCAN_STEP_KINDS)
    assert kw["steps_pad"] > 0
    assert kw["pairs_per_dev"] > 0 and kw["tiles_per_dev"] > 0
    assert ("plan", {"nprobe": NPROBE}) in seen


def test_steps_are_counted_with_tracing_off(
    engine, clustered_data, monkeypatch
):
    """No tracer: the counter still counts, and no annotation is made."""
    qs = clustered_data[2]
    made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **kw: made.append(a))
    srv = ServingEngine(engine, nprobe=NPROBE, k=K, micro_batch=MB)
    srv.warmup()
    srv.search(qs[:MB])
    c = _counter(srv.stats)
    assert c["query"] > 0 and c["pad"] == 0
    assert made == []


def test_compiled_steps_carry_the_named_scopes(engine, clustered_data):
    """The stages' scopes reach the compiled ops' `op_name` metadata, which
    a device profile shows as each op's `tf_op`."""
    qs = clustered_data[2]
    plan = engine.plan_batch(_padded(qs, 4), NPROBE)
    text = engine.compiled_search_text(plan, K)
    for scope in ("lut", "local_merge", "global_merge"):
        assert re.search(rf'op_name="[^"]*/{scope}/', text), scope

    ndev = engine.shards.ndev
    raw = jnp.zeros((ndev, 64, 32), jnp.float32)
    ids = jnp.zeros((64,), jnp.int32)
    text = sharded_rerank.lower(
        raw, ids, ids, jnp.zeros((4, 32), jnp.float32),
        jnp.zeros((4, 16), jnp.int32), mesh=make_dpu_mesh(), k_out=K,
    ).compile().as_text()
    for scope in ("rerank_gather", "rerank_select"):
        assert re.search(rf'op_name="[^"]*/{scope}/', text), scope
