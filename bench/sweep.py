#!/usr/bin/env python3
"""The open-loop knee of a cell: served rate and latency at offered rates.

    python bench/sweep.py --workload <cell> --rates 100,150,200 --seconds 20

Sets the cell up once (corpus, build, warm-up), then serves the cell's
open-loop mix at each offered rate in turn, for `--seconds` each, with
the mix's bursts, and prints one JSON line per rate: offered and served
queries per second, p50 and p99 latency (due to answered), and the mean
wait in each third of the run (a wait that grows from third to third is
a backlog that grows).  The rate a cell's mix file names is 0.8 x the
highest rate whose backlog does not grow.  A benchmark run never runs
this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import cell as cell_mod  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    c = cell_mod.load_cell(args.workload, run.ROOT)
    if c.mix.arrivals != "open_loop":
        print(f"sweep: {c.name} has no open-loop mix", file=sys.stderr)
        return 2
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.launch.env import setup_env

    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    setup_env(platform="tpu")
    import jax
    import numpy as np

    import drive
    from traffic.generate import arrival_times
    from traffic.vectors import VectorModel, VectorSource

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    cfg = c.config
    rates = [float(r) for r in args.rates.split(",")]
    t = time.perf_counter()
    src = VectorSource(VectorModel.from_config(cfg),
                       cfg["data"]["corpus_seed"])
    base = src.corpus(cfg["n_vectors"])
    history = src.queries(cfg["build"]["history_queries"], stream=0)
    eng = run.build_system(cfg, c.chips, base, history,
                           cfg["data"]["corpus_seed"])
    srv = run.serving(cfg, eng, c.mix.micro_batch, None)
    srv.warmup()
    print(json.dumps({"setup_s": time.perf_counter() - t}), flush=True)
    for rate in rates:
        mix = dataclasses.replace(c.mix, rate_qps=rate)
        due = arrival_times(mix, args.seconds, args.seed)
        pool = src.queries(len(due), stream=1,
                           seed=args.seed).astype("float32")
        win = drive.open_loop(srv, pool, due, mix.micro_batch)
        thirds = np.array_split(np.arange(len(due)), 3)
        print(json.dumps({
            "offered_qps": rate,
            "served_qps": win.answered / win.elapsed_s,
            "p50_ms": float(np.percentile(win.latency_s, 50) * 1e3),
            "p99_ms": float(np.percentile(win.latency_s, 99) * 1e3),
            "mean_wait_ms_by_third": [float(win.wait_s[p].mean() * 1e3)
                                      for p in thirds],
            "calls": win.calls,
            "median_call_s": float(np.median(win.call_s)),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
