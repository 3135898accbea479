"""A cell's definition, found by name from BENCHMARK.json and data files.

`BENCHMARK.json` names each cell's configuration and traffic mix and each
metric.  Everything else is found by name, so a later change adds a cell
or a metric by adding files:

  * a configuration: the JSON file its `configs` entry names;
  * a traffic mix: `bench/traffic/<traffic>.json`;
  * a metric: `bench/metrics/<metric name>.py`, which defines
    `read(ctx) -> float | None` (None: nothing to read in this run).

Paths are relative to the checkout's root.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

from traffic.generate import Mix



@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file's contents
    mix: Mix
    end_to_end: list       # BENCHMARK.json metric entries of this cell
    per_layer: list


def _metric_cells(metric: dict, workloads: list, e2e: list) -> list:
    if "workloads" in metric:
        return list(metric["workloads"])
    if "moves" in metric:  # per-layer: every cell reporting what it moves
        mover = next(m for m in e2e if m["name"] == metric["moves"])
        return _metric_cells(mover, workloads, e2e)
    return [w["name"] for w in workloads]


def load_cell(name: str, root: Path) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = Mix.load(root / "bench" / "traffic" / f"{w['traffic']}.json")
    e2e = bench["end_to_end"]

    def mine(metrics):
        return [m for m in metrics
                if name in _metric_cells(m, bench["workloads"], e2e)]

    return Cell(name=name, chips=int(w["chips"]), config=cfg, mix=mix,
                end_to_end=mine(e2e), per_layer=mine(bench["per_layer"]))


def reader(metric_name: str, root: Path):
    """The `read` function of `bench/metrics/<metric_name>.py`."""
    path = root / "bench" / "metrics" / f"{metric_name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {metric_name!r} has no reader at "
                                f"{path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
