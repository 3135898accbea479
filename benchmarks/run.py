"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only fig13] [--json BENCH_5.json]``

Prints ``name,us_per_call,derived`` CSV rows (plus a header).  Times are
wall-clock on whatever backend runs the harness, stamped on every row: only
rows measured on a TPU are device numbers.  On the CPU (Pallas interpret
mode, fake devices) they are relative signals between code paths, never
device metrics.

``--json PATH`` additionally records every emitted row in a machine-readable
file (per-sub-bench QPS / latency / rows-scanned / tiles-skipped and any
other ``key=value`` pairs from the derived column), MERGING into an existing
file so CI steps that run different ``--only`` slices accumulate one
``BENCH_<pr>.json`` artifact tracking the perf trajectory across PRs.

Every row is stamped with the measurement context (``backend`` /
``device_kind`` / ``autotune`` mode).  Rows measured on a TPU that report
their ideal probed-code bytes (``ideal_bytes=...`` in the derived column)
gain a ``roofline_frac`` column -- (ideal_bytes / HBM bandwidth) / measured
seconds, peaks resolved per device kind via
`repro.launch.roofline_report.peaks_for` with ``peaks_source`` recorded
next to it.  Rows from any other backend carry no roofline column.
`repro.launch.env.setup_env` runs before jax initializes (XLA flags,
platform defaults and the compile cache; CI's pinned env always wins).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

MODULES = [
    ("bench_breakdown", "Fig 1/18 stage breakdown"),
    ("bench_placement", "Fig 4/7 skew + placement balance"),
    ("bench_cooc", "Fig 10 + Table 1 co-occurrence + churn-stream QPS"),
    ("bench_qps", "Fig 13 QPS vs baseline + pipelined serving"),
    ("bench_scaling", "Fig 14 scaling with #devices"),
    ("bench_read_size", "Fig 9/15 MRAM-read-size analogue"),
    ("bench_threads", "Fig 16 tasklet analogue"),
    ("bench_topk", "Fig 12/17 top-k size + pruning"),
    ("bench_tiles", "tile-list vs padded-window device scan"),
    ("bench_prune", "early-pruning v2: bound-driven tile skips"),
    ("bench_mutation", "insert/delete churn QPS + compaction latency"),
    ("bench_recall_frontier", "recall@k vs QPS: PQ-only vs exact re-rank"),
    ("bench_autotune", "kernel-geometry sweep vs default + cache reuse"),
    ("bench_faults", "QPS + recall under device death and overload"),
]


def _parse_derived(derived: str) -> dict:
    """'a=1;b=x' -> {'a': 1.0, 'b': 'x'} (floats where they parse)."""
    out: dict = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        key, val = part.split("=", 1)
        try:
            out[key] = float(val)
        except ValueError:
            out[key] = val
    return out


def write_json(
    path: str,
    rows,
    errors: dict | None = None,
    meta: dict | None = None,
) -> None:
    """Merge benchmark rows into `path` (rows keyed by bench name).

    `errors` maps module name -> exception string for modules that raised;
    each lands as a ``{"error": ...}`` row so a partial run is visible in
    the artifact instead of silently absent (a module that emitted some
    rows before raising keeps those rows AND gains the error marker).

    `meta` is the measurement context (backend / device_kind / autotune /
    peaks): stamped onto the document AND onto every row written this
    call, and used to derive ``roofline_frac`` for rows carrying their
    ideal byte traffic.
    """
    doc = {"schema": 1, "rows": {}}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            if isinstance(prev, dict) and isinstance(prev.get("rows"), dict):
                doc = prev
        except (OSError, json.JSONDecodeError):
            pass  # unreadable previous artifact: start fresh
    meta = meta or {}
    stamp = {
        k: meta[k]
        for k in ("backend", "device_kind", "autotune")
        if k in meta
    }
    if meta:
        doc["meta"] = {**doc.get("meta", {}), **meta}
    for name, us_per_call, derived, *extra in rows:
        row = {
            "us_per_call": us_per_call,
            **_parse_derived(derived),
            **stamp,
        }
        if extra and extra[0]:
            # observability stamp (metrics snapshot + per-phase wall-time
            # breakdown) attached via benchmarks.common.emit(stats=...)
            row["metrics"] = extra[0]
        # roofline fraction: ideal code-stream seconds / measured seconds
        # (only for TPU rows that report their ideal byte traffic)
        hbm_bw = meta.get("hbm_bw")
        if (
            meta.get("backend") == "tpu" and hbm_bw
            and row.get("ideal_bytes") and us_per_call > 0
        ):
            row["roofline_frac"] = (
                row["ideal_bytes"] / hbm_bw / (us_per_call * 1e-6)
            )
            row["peaks_source"] = meta["peaks_source"]
        doc["rows"][name] = row
    for mod_name, msg in (errors or {}).items():
        doc["rows"][mod_name] = {
            **doc["rows"].get(mod_name, {}), "error": msg,
        }
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="substring filter")
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="merge emitted rows into a machine-readable BENCH_<pr>.json",
    )
    ap.add_argument(
        "--keep-going", action="store_true",
        help="run every sub-bench even after a failure (still exits "
             "non-zero); the default aborts on the first raise",
    )
    ap.add_argument(
        "--autotune", choices=["off", "cache", "sweep"], default="off",
        help="kernel-geometry autotune mode benches construct serving "
             "engines with (default off: bench rows measure the build-time "
             "geometry unless a bench sweeps explicitly); the mode is "
             "stamped onto every emitted row",
    )
    args = ap.parse_args()
    # env defaults must land before `benchmarks.common` imports jax
    from repro.launch.env import describe_env, setup_env

    setup_env()

    from benchmarks import common

    common.AUTOTUNE_MODE = args.autotune
    from repro.launch.roofline_report import peaks_for

    env = describe_env()
    meta = {
        "backend": env["backend"],
        "device_kind": env["device_kind"],
        "n_devices": env["n_devices"],
        "autotune": args.autotune,
    }
    peaks_source = "none"
    if env["backend"] == "tpu":  # roofline shares only for device rows
        peak_flops, hbm_bw, peaks_source = peaks_for(env["device_kind"])
        meta.update(
            peak_flops=peak_flops, hbm_bw=hbm_bw, peaks_source=peaks_source
        )

    print("name,us_per_call,derived")
    print(
        f"# backend={env['backend']} device_kind={env['device_kind']} "
        f"n_devices={env['n_devices']} autotune={args.autotune} "
        f"peaks={peaks_source}"
    )
    failures: dict[str, str] = {}
    for mod_name, desc in MODULES:
        if args.only and args.only not in mod_name:
            continue
        print(f"# {mod_name}: {desc}", flush=True)
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            mod.run()
        except Exception as exc:  # noqa: BLE001
            traceback.print_exc()
            failures[mod_name] = f"{type(exc).__name__}: {exc}"
            if not args.keep_going:
                # record whatever completed before the raise + the error
                # marker, so partial runs are visible in the artifact
                if args.json:
                    write_json(args.json, common.ROWS, failures, meta)
                print(f"# FAILED: {mod_name} (fail-fast; use --keep-going "
                      f"to run the rest)")
                sys.exit(1)
        if args.json:
            # incremental merge after every module: a later hard crash
            # (OOM, SIGKILL) cannot drop rows already measured
            write_json(args.json, common.ROWS, failures, meta)
    if args.json:
        write_json(args.json, common.ROWS, failures, meta)
        print(f"# wrote {len(common.ROWS)} rows to {args.json}")
    if failures:
        print(f"# FAILED: {sorted(failures)}")
        sys.exit(1)
    print("# all benchmarks completed")


if __name__ == "__main__":
    main()
