"""The one traffic generator: a mix file's parameters -> a request plan.

Two arrival processes, chosen by the mix's `arrivals`:

  * "backlog": requests of `request_queries` queries, always one ready
    (closed loop at depth one: the next request is sent as the previous
    answer returns).  The queries of `pool_requests` distinct requests
    are made up front and served in turn for as long as the window lasts.
  * "open_loop": single-query requests due at seeded times.  The rate is
    `rate_qps` on average; in every `burst_period_s` the `burst_s` seconds
    from `burst_start_s` on run at `burst_factor` times it, the rest at
    the rate that keeps the mean.  A run of `seconds` seconds holds
    exactly round(rate_qps * seconds) arrivals, placed by that density:
    every seed gets the same count and the same bursts, at other times.
    The server forms a micro-batch of everything due, up to
    `micro_batch` queries, whenever it is free.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

KINDS = ("backlog", "open_loop")


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    arrivals: str
    micro_batch: int
    request_queries: int = 1
    pool_requests: int = 0
    rate_qps: float = 0.0
    burst_period_s: float = 0.0
    burst_start_s: float = 0.0
    burst_s: float = 0.0
    burst_factor: float = 1.0

    @classmethod
    def load(cls, path: Path) -> "Mix":
        raw = json.loads(Path(path).read_text())
        fields = {f.name for f in dataclasses.fields(cls)}
        mix = cls(name=Path(path).stem,
                  **{k: v for k, v in raw.items() if k in fields})
        if mix.arrivals not in KINDS:
            raise ValueError(f"{path}: arrivals must be one of {KINDS}")
        return mix

    def query_count(self, seconds: float) -> int:
        """Distinct queries a run of `seconds` needs made up front."""
        if self.arrivals == "backlog":
            return self.pool_requests * self.request_queries
        return int(round(self.rate_qps * seconds))


def _rate_edges(mix: Mix, seconds: float) -> tuple[np.ndarray, np.ndarray]:
    """Piecewise-constant rate over [0, seconds): (edges, rates)."""
    edges = [0.0]
    if mix.burst_period_s > 0 and mix.burst_s > 0:
        t = mix.burst_start_s
        while t < seconds:
            edges += [t, min(t + mix.burst_s, seconds)]
            t += mix.burst_period_s
    edges = np.unique(np.clip(np.asarray(edges + [seconds]), 0.0, seconds))
    mid = (edges[:-1] + edges[1:]) / 2
    inside = np.zeros(len(mid), bool)
    if mix.burst_period_s > 0 and mix.burst_s > 0:
        phase = (mid - mix.burst_start_s) % mix.burst_period_s
        inside = (mid >= mix.burst_start_s) & (phase < mix.burst_s)
    share = mix.burst_s / mix.burst_period_s if mix.burst_period_s else 0.0
    rest = (1.0 - share * mix.burst_factor) / max(1.0 - share, 1e-12)
    rates = np.where(inside, mix.burst_factor, rest) * mix.rate_qps
    return edges, rates


def arrival_times(mix: Mix, seconds: float, seed: int) -> np.ndarray:
    """Sorted due times (s from the window's start) of an open-loop run."""
    n = mix.query_count(seconds)
    edges, rates = _rate_edges(mix, seconds)
    mass = np.concatenate([[0.0], np.cumsum(rates * np.diff(edges))])
    u = np.sort(np.random.default_rng([int(seed), 11]).random(n)) * mass[-1]
    seg = np.clip(np.searchsorted(mass, u, side="right") - 1, 0,
                  len(rates) - 1)
    return edges[seg] + (u - mass[seg]) / rates[seg]
