#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell (a configuration under a
traffic mix) is found by name in BENCHMARK.json; see `cell.py`.

A run:

  1. set-up: makes the corpus on the device from the configuration's
     corpus seed and the queries from `--seed` (`traffic/vectors.py`),
     builds the index with `MemANNSEngine.build` (on the host, through
     JAX's CPU backend, where the configuration's `build.device` says
     "cpu") and warms `ServingEngine` for the cell's micro-batch; the
     build, the warm-up and the window run the program's products at the
     configuration's `serving.matmul_precision`; then freezes the
     collector's view of set-up's objects (`gc.freeze`), as a long-lived
     server does after loading;
  2. window: serves the mix for `--seconds` seconds (`drive.py`); with
     `--trace 1` under the JAX profiler, with the program's spans in the
     trace;
  3. reads the device's peak memory, frees the program, judges the
     program's lists and codes and a seeded sample of the window's
     answers against the plain reference (`check.py`, `reference.py`).

The corpus, and so every compiled shape, is the same for every seed: a
cell's second run in a checkout compiles nothing.

Standard output carries facts (set-up by phase, compiles in the window,
collector pauses and the slowest calls of the window, generator
lateness, recall@10 against brute force) and, as its last line,
one JSON object: `correct`, `attempted`, `failed`, `metrics` (the cell's
end-to-end metrics, or with `--trace 1` its per-layer metrics), `device`,
with `--trace 1` `breakdown`, and last `checks`, each compared number
beside its limit.  The same numbers end standard error.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""

from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import cell as cell_mod  # noqa: E402

# host spans a trace attributes idle device time to: the program's
# serving spans and the harness's own
SPANS = ("plan", "schedule", "densify", "emit_tiles", "delta", "dispatch",
         "rerank_dispatch", "collect", "merge", "generator", "result")


def process_start() -> float:
    """`time.perf_counter()` reading of the moment the process started
    (from /proc; the import of this module where that is unreadable)."""
    try:
        stat = Path("/proc/self/stat").read_text()
        ticks = int(stat.rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def fact(name: str, **values) -> None:
    """One fact of the run on its own line (not a metric)."""
    print(f"bench fact {name}: {json.dumps(values, sort_keys=True)}",
          flush=True)


class CompileCounter:
    """Counts lowerings (each a program built or loaded from the
    persistent cache), persistent-cache hits and misses, and the seconds
    spent compiling and reading the cache."""

    def __init__(self):
        import jax

        self.lowered = self.hits = self.misses = 0
        self.compile_s = self.retrieve_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieve_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"lowered": self.lowered, "cache_hits": self.hits,
                "cache_misses": self.misses, "compile_s": self.compile_s,
                "cache_retrieval_s": self.retrieve_s}


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def hbm_bytes_per_s(device_kind: str) -> float:
    """HBM bandwidth of `device_kind` from `peaks.json`; raises for a kind
    the table does not hold."""
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; known: {sorted(peaks)}")
    return float(peaks[device_kind]["hbm_bytes_per_s"])


def cpu_build(cfg: dict) -> bool:
    """Whether the configuration builds its index offline on the host,
    through JAX's CPU backend, and serves it from the chip."""
    return cfg["build"].get("device") == "cpu"


def build_system(cfg: dict, chips: int, base, history, seed: int):
    """`MemANNSEngine.build` at the configuration's settings; `seed` is
    the corpus seed."""
    from repro.launch.mesh import make_retrieval_mesh
    from repro.retrieval import MemANNSEngine
    from traffic.vectors import seed_key

    b = cfg["build"]
    return MemANNSEngine.build(
        seed_key(seed, 9), base.astype("float32"), cfg["n_clusters"],
        cfg["m"], mesh=make_retrieval_mesh(chips),
        history_queries=history.astype("float32"),
        nprobe_history=cfg["nprobe"], use_cooc=b["use_cooc"],
        n_combos=cfg["n_combos"], block_n=cfg["block_n"],
        kmeans_iters=b["kmeans_iters"], pq_iters=b["pq_iters"],
        train_subsample=b["train_subsample"], rerank=cfg["rerank"],
        rerank_block=b["rerank_block"], store_raw=cfg["rerank"] == "exact",
        raw_dtype=b["raw_dtype"], scan=b["scan"], path=b["path"],
        prune=b["prune"],
    )


def serving(cfg: dict, eng, micro_batch: int, tracer):
    from repro.retrieval import ServingEngine

    s = cfg["serving"]
    return ServingEngine(
        eng, nprobe=cfg["nprobe"], k=cfg["k"], micro_batch=micro_batch,
        pipeline_depth=s["pipeline_depth"], autotune=s["autotune"],
        deadline_ms=s["deadline_ms"], queue_limit=s["queue_limit"],
        tracer=tracer,
    )


class GcPauses:
    """The collector's pauses while `active` (gc.callbacks)."""

    def __init__(self):
        self.active = False
        self.pauses: list = []   # (start s, seconds, generation)
        self._t = 0.0
        gc.callbacks.append(self._hook)

    def _hook(self, phase, info):
        if not self.active:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((self._t, time.perf_counter() - self._t,
                                info["generation"]))

    def close(self):
        gc.callbacks.remove(self._hook)


def run_cell(c: cell_mod.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False) -> dict:
    """Set up, serve the window, judge it; returns the result object.

    On another backend than a TPU (tests drive it on the CPU) the result
    carries no metric and no device measurement.  `control=True` (used by
    `control.py`, never by a benchmark run) also judges the control -- the
    reference in bfloat16, its lists, codes and answers, put in the
    program's place -- on the same sample, under `control_checks`."""
    import jax
    import numpy as np

    import check
    import drive
    import reference
    from traffic.generate import arrival_times
    from traffic.vectors import VectorModel, VectorSource

    cfg, mix = c.config, c.mix
    devices = jax.devices()
    on_chip = devices[0].platform == "tpu"
    compiles = CompileCounter()
    phases = {"start": time.perf_counter() - t_start}

    t = time.perf_counter()
    corpus_seed = cfg["data"]["corpus_seed"]
    src = VectorSource(VectorModel.from_config(cfg), corpus_seed)
    base = src.corpus(cfg["n_vectors"])
    history = src.queries(cfg["build"]["history_queries"], stream=0)
    pool = src.queries(mix.query_count(seconds), stream=1,
                       seed=seed).astype("float32")
    due = (arrival_times(mix, seconds, seed)
           if mix.arrivals == "open_loop" else None)
    phases["data"] = time.perf_counter() - t

    # the program's own matrix products at the precision the configuration
    # states (JAX's default where it states none), for the build, the
    # warm-up and the window; an explicit precision in the program wins
    jax.config.update("jax_default_matmul_precision",
                      cfg["serving"].get("matmul_precision"))
    t = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0] if cpu_build(cfg)
                            else None):
        eng = build_system(cfg, c.chips, base, history, corpus_seed)
    phases["build"] = time.perf_counter() - t
    k_cand = eng.k_prime(cfg["k"]) if cfg["rerank"] == "exact" else cfg["k"]
    if cfg["rerank"] == "exact" and k_cand != cfg["k_prime"]:
        raise ValueError(f"engine k' {k_cand} != configured {cfg['k_prime']}")

    t = time.perf_counter()
    tracer = None
    if trace:
        from repro.obs.trace import Tracer

        tracer = Tracer(profiler=True)
    srv = serving(cfg, eng, mix.micro_batch, tracer)
    buckets = srv.warmup()
    phases["warmup"] = time.perf_counter() - t
    setup_compiles = compiles.snapshot()

    plans: list = []
    if trace:  # tile lists of the window's plans, for the roofline count
        plan_batch = eng.plan_batch

        def recording_plan_batch(*a, **kw):
            plan = plan_batch(*a, **kw)
            if plan.tile_pair is not None:
                plans.append((plan.tile_pair, plan.tile_block,
                              plan.pairs_per_dev))
            return plan

        eng.plan_batch = recording_plan_batch

    st = srv.stats
    before = (st.batches, st.tiles_dispatched, st.tiles_skipped,
              st.phase_seconds("plan"), st.compiles, compiles.lowered)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    span = _annotate if trace else drive.no_span
    if trace:  # host spans only: the runtime's own host events are many
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    setup_s = time.perf_counter() - t_start
    pauses.active = True
    with span("window"):
        if mix.arrivals == "backlog":
            win = drive.backlog(srv, pool, mix.request_queries, seconds, span)
        else:
            win = drive.open_loop(srv, pool, due, mix.micro_batch, span)
    pauses.active = False
    pauses.close()
    if trace:
        jax.profiler.stop_trace()
    jax.config.update("jax_default_matmul_precision", None)
    after = (st.batches, st.tiles_dispatched, st.tiles_skipped,
             st.phase_seconds("plan"), st.compiles, compiles.lowered)
    delta = [a - b for a, b in zip(after, before)]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    fact("setup", seconds=setup_s, phases=phases, pair_buckets=buckets,
         compile_events=setup_compiles)
    fact("window", seconds=win.elapsed_s, calls=win.calls,
         micro_batches=delta[0], answered=win.answered,
         steady_state_compiles=delta[4], lowered_in_window=delta[5],
         call_s=np.round(win.call_s, 4).tolist(),
         bucket_hits={str(k): v for k, v in st.bucket_hits.items()})
    slow = np.argsort(win.call_s)[::-1][:5]
    fact("slowest_calls", median_s=float(np.median(win.call_s)),
         start_s=[float(win.call_t[j]) for j in slow],
         seconds=[float(win.call_s[j]) for j in slow])
    fact("gc", collections=len(pauses.pauses),
         by_generation={str(g): sum(1 for p in pauses.pauses if p[2] == g)
                        for g in range(3)},
         total_s=float(sum(p[1] for p in pauses.pauses)),
         longest=[[float(p[0] - win.t0), float(p[1]), p[2]] for p in
                  sorted(pauses.pauses, key=lambda p: -p[1])[:5]])
    if win.late_s is not None:
        fact("generator", wakes=int(win.late_s.size),
             late_mean_s=float(win.late_s.mean()) if win.late_s.size else 0.0,
             late_max_s=float(win.late_s.max()) if win.late_s.size else 0.0)

    # free the program before the reference runs; keep its lists and
    # codes as data to be judged
    ix = eng.index
    program_index = reference.PlainIndex(
        centroids=np.asarray(ix.centroids, np.float32),
        codebook=np.asarray(ix.codebook, np.float32),
        codes=ix.codes, ids=ix.vec_ids.astype(np.int64),
        offsets=ix.offsets.astype(np.int64))
    block_n = eng.shards.block_n
    tile_bytes = block_n * eng.shards.width * eng.shards.codes.dtype.itemsize
    del srv, eng, ix
    gc.unfreeze()
    gc.collect()

    t = time.perf_counter()
    ck = cfg["check"]
    index, build_numbers = reference.build_index(program_index, base,
                                                 ck["build_tie"])
    t_build = time.perf_counter() - t
    pos = check.sample_positions(win.answered, ck["sample_queries"], seed)
    qs = pool[win.query_index[pos]]
    numbers, bad_queries = check.compare(
        index, base, qs, win.ids[pos], win.dists[pos], cfg=cfg)
    numbers.update(build_numbers)
    fact("check", sampled=int(len(pos)), faulty_queries=bad_queries,
         build_seconds=t_build,
         reference_seconds=time.perf_counter() - t)
    t = time.perf_counter()
    n_rec = min(cfg["check"]["recall_queries"], len(pos))
    recall = check.recall_at_k(base, qs[:n_rec], win.ids[pos][:n_rec],
                               cfg["k"])
    fact("recall", queries=n_rec, k=cfg["k"], recall_at_k=recall,
         brute_force_seconds=time.perf_counter() - t)
    control_numbers = None
    if control:
        t = time.perf_counter()
        rerank = cfg["rerank"] == "exact"
        own = reference.own_index(program_index.centroids,
                                  program_index.codebook, base, "bfloat16")
        answers = [reference.answer(
            own, base, q, nprobe=cfg["nprobe"], k=cfg["k"],
            k_cand=k_cand, rerank=rerank, precision="bfloat16") for q in qs]
        control_numbers, control_bad = check.compare(
            index, base, qs, np.stack([a[1] for a in answers]),
            np.stack([a[0] for a in answers]), cfg=cfg)
        control_numbers.update(
            reference.build_index(own, base, ck["build_tie"])[1])
        fact("control", faulty_queries=control_bad,
             seconds=time.perf_counter() - t)

    reduced = None
    if trace:
        import trace_reduce

        try:
            reduced = trace_reduce.reduce_profile(
                trace_reduce.find_xplane(log_dir), SPANS)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)

    ctx = types.SimpleNamespace(
        cell=c, config=cfg, mix=mix, seconds=seconds, setup_s=setup_s,
        window=win, peak_bytes=peak, micro_batches=delta[0],
        tiles_dispatched=delta[1], tiles_skipped=delta[2], plan_s=delta[3],
        plans=plans, tile_bytes=tile_bytes, trace=reduced,
        hbm_bytes_per_s=lambda: hbm_bytes_per_s(devices[0].device_kind),
    )
    metrics = {}
    for m in (c.per_layer if trace else c.end_to_end):
        value = cell_mod.reader(m["name"], ROOT)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    limits = cfg["check"]["limits"]
    result = {
        "correct": check.within(numbers, limits) and bad_queries == 0,
        "attempted": win.answered,
        "failed": bad_queries,
        "metrics": metrics if on_chip else {},
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
    }
    if on_chip:
        result["device"]["memory_peak_bytes"] = int(peak)
    if reduced is not None and on_chip:
        result["device"]["busy_s"] = reduced.busy_s
        result["device"]["window_s"] = reduced.window_s
        ops = sorted(reduced.kernel_s.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(reduced.idle_by_label().items(),
                      key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": [list(x) for x in ops],
                               "idle_gaps": [list(x) for x in idle]}
    if control_numbers is not None:
        result["control_checks"] = {
            n: {"value": control_numbers[n], "limit": limits[n]}
            for n in limits}
    result["checks"] = {n: {"value": numbers[n], "limit": limits[n]}
                        for n in limits}
    return result


def main(argv=None) -> int:
    t_start = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        c = cell_mod.load_cell(args.workload, ROOT)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.env import setup_env
    except ImportError:
        print("bench: no src/repro in this checkout", file=sys.stderr)
        return 2
    # every program goes into the persistent cache, kept inside this
    # checkout at a fixed path whatever the machine sets, so a second run
    # of a cell compiles nothing and two checkouts share no cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    if cpu_build(c.config):  # the CPU backend beside the TPU, for the build
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    setup_env(platform="tpu")
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: no TPU: {e}", file=sys.stderr)
        return 3
    if devices[0].platform != "tpu" or len(devices) < c.chips:
        print(f"bench: {c.name} needs {c.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    result = run_cell(c, args.seed, args.seconds, bool(args.trace), t_start)
    print(f"correct: {result['correct']}", file=sys.stderr)
    for name, v in result["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
