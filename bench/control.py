#!/usr/bin/env python3
"""Program and control readings of the numbers `correct` compares.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

Runs the cell once per seed in one process (set-up, a window at the
cell's own load, the check) and judges, on the same seeded sample, both
the program's lists, codes and answers and the control's: the plain
reference computed in bfloat16, put in the program's place.  Prints one
JSON line per seed.  The limits in the configuration's `check.limits` are
set from these readings: above the largest the program gives, below the
smallest the control gives.

`--matmul-precision highest` runs the program, build and window, under
JAX's `default_matmul_precision("highest")`, whatever the configuration
states: a witness of whether a
fault the readings show comes from matrix products computed in one
bfloat16 pass.  A benchmark run never runs this file.
"""

from __future__ import annotations

import argparse
import contextlib
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--matmul-precision", default=None)
    args = ap.parse_args(argv)
    c = cell_mod.load_cell(args.workload, run.ROOT)
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.launch.env import setup_env

    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    if run.cpu_build(c.config):
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    setup_env(platform="tpu")
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 3
    for seed in [int(s) for s in args.seeds.split(",")]:
        with contextlib.ExitStack() as stack:
            if args.matmul_precision:
                stack.enter_context(
                    jax.default_matmul_precision(args.matmul_precision))
            res = run.run_cell(c, seed, args.seconds, False,
                               time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "matmul_precision": args.matmul_precision,
                          "program": res["checks"],
                          "control": res["control_checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
