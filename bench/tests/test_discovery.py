"""A configuration, a traffic mix and a metric that are only added --
data files plus their entries in BENCHMARK.json, no harness file edited
-- are found by the harness."""

import json

import cell
import checkout


def test_added_files_are_found(tmp_path):
    root = checkout.make(tmp_path)
    metric = root / "bench" / "metrics" / "tiny_answered.py"
    metric.write_text("def read(ctx):\n    return ctx.window.answered\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    name = f"{checkout.TINY}.tiny_backlog"
    bench["per_layer"].append({
        "name": "tiny_answered", "unit": "queries", "better": "higher",
        "source": "host_clock", "layer": "serving loop", "moves": "setup_s",
        "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    c = cell.load_cell(name, root)
    assert c.config["name"] == checkout.TINY and c.config["dim"] == 32
    assert c.mix.arrivals == "backlog" and c.mix.request_queries == 64
    assert [m["name"] for m in c.per_layer] == ["tiny_answered"]
    assert {m["name"] for m in c.end_to_end} == {"hbm_peak_gb", "setup_s"}

    class Ctx:
        class window:
            answered = 17

    assert cell.reader("tiny_answered", root)(Ctx) == 17


def test_every_benchmark_metric_has_a_reader():
    for group in ("end_to_end", "per_layer"):
        for m in json.loads((checkout.REPO / "BENCHMARK.json")
                            .read_text())[group]:
            assert callable(cell.reader(m["name"], checkout.REPO))


def test_cells_of_the_benchmark_load():
    bench = json.loads((checkout.REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        c = cell.load_cell(w["name"], checkout.REPO)
        assert c.end_to_end and c.per_layer
        assert any(m["name"] == "setup_s" for m in c.end_to_end)
