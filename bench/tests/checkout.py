"""A throwaway checkout for tests: BENCHMARK.json and bench/ copied, the
program's src/ linked, and the test-only tiny cell added as data files."""

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
TINY = "tiny_ivf64_pq8"


def make(tmp: Path, mixes=("tiny_backlog",)) -> Path:
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(DATA / f"{TINY}.json", root / "bench" / "configs")
    bench["configs"].append({
        "name": TINY, "source": "test-only", "reduced": [],
        "file": f"bench/configs/{TINY}.json", "why": "test-only"})
    for mix in mixes:
        shutil.copy(DATA / f"{mix}.json", root / "bench" / "traffic")
        bench["workloads"].append({
            "name": f"{TINY}.{mix}", "config": TINY, "traffic": mix,
            "chips": 1, "why": "test-only"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
