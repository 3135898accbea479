"""The program's process-lifetime counters, for per-layer readers.

A reader runs after `run.run_cell` has freed the serving engine, so it
reads the program's `repro.obs.metrics.PROCESS_REGISTRY`: the counter
families an engine also adds there, summed over every engine the process
has run.  A benchmark run serves one engine, and its set-up (the build
and `ServingEngine.warmup`) collects no micro-batch, so a series there
holds the window's count.  Where the program has no such registry or no
such series, as an older program has not, a lookup reads None."""


def counter(name: str, **labels) -> float | None:
    """Value of the counter series `name{labels}`; None where absent."""
    try:
        from repro.obs.metrics import PROCESS_REGISTRY
    except ImportError:
        return None
    fam = PROCESS_REGISTRY.snapshot().get(name)
    if fam is None or fam["type"] != "counter":
        return None
    want = {k: str(v) for k, v in labels.items()}
    for s in fam["samples"]:
        if {k: str(v) for k, v in s["labels"].items()} == want:
            return float(s["value"])
    return None


def scan_steps(kinds=("query", "pad", "bucket")) -> list | None:
    """`upanns_scan_steps_total` of each of `kinds`; None where any is
    absent or none was counted."""
    steps = [counter("upanns_scan_steps_total", kind=k) for k in kinds]
    if None in steps or sum(steps) <= 0:
        return None
    return steps
