"""The comparison that decides `correct`.

The program's lists and codes are judged row by row against the
reference's own (`reference.build_index`: `misassigned`, `miscoded`,
`lost_rows`).  Then a sample of the window's answered queries, drawn from
the seed, is judged against `reference.truth` over the reference index
once the window has closed.  Each number is held to the limit the
configuration's `check.limits` gives; from the answers:

  * the distance gap: for every returned id, how far the returned
    distance lies from the reference's distance of that id -- relative
    (`adc_rel_gap`) for ADC answers, absolute (`exact_gap`) for exact
    re-ranked answers, whose reference distances are exact integers;
  * `missed`: required ids that were not returned (for a re-rank, those
    whose exact distance lies below the returned k-th);
  * `bad_ids`: returned slots that hold no admissible id: -1, a repeat,
    an id outside the probed clusters, or a non-finite distance.
"""

from __future__ import annotations

import math

import numpy as np

import reference


def judge(t: reference.QueryTruth, ids: np.ndarray,
          dists: np.ndarray) -> tuple[float, int, int]:
    """(distance gap, missed, bad_ids) of one query's answer."""
    seen: set[int] = set()
    gap, bad = 0.0, 0
    for i, d in zip(np.asarray(ids).tolist(), np.asarray(dists).tolist()):
        r = t.admissible.get(i)
        if r is None or i in seen or not math.isfinite(d):
            bad += 1
            continue
        seen.add(i)
        gap = max(gap, abs(d - r) if t.exact else abs(d - r) / max(abs(r), 1e-30))
    kth = max((d for d in np.asarray(dists).tolist() if math.isfinite(d)),
              default=math.inf)
    missed = 0
    for i, rd in zip(t.required.tolist(), t.required_dist.tolist()):
        if i not in seen and (not t.exact or rd < kth):
            missed += 1
    return gap, missed, bad


def gap_name(rerank: bool) -> str:
    return "exact_gap" if rerank else "adc_rel_gap"


def sample_positions(n_answered: int, n_sample: int, seed: int) -> np.ndarray:
    """Sorted positions of the judged answers, drawn from the seed."""
    rng = np.random.default_rng([int(seed), 7])
    n = min(n_sample, n_answered)
    return np.sort(rng.choice(n_answered, n, replace=False))


def compare(index: reference.PlainIndex, base: np.ndarray,
            queries: np.ndarray, ids: np.ndarray, dists: np.ndarray, *,
            cfg: dict) -> tuple[dict, int]:
    """Judge every row of (queries, ids, dists); returns ({number: value},
    queries with any fault)."""
    rerank = cfg["rerank"] == "exact"
    ck = cfg["check"]
    gap, missed, bad, failed = 0.0, 0, 0, 0
    for q, i, d in zip(queries, ids, dists):
        t = reference.truth(
            index, base, q, nprobe=cfg["nprobe"], k=cfg["k"],
            k_cand=cfg["k_prime"] if rerank else cfg["k"], rerank=rerank,
            probe_tie=ck["probe_tie"], adc_tie=ck["adc_tie"])
        g, m, b = judge(t, i, d)
        gap, missed, bad = max(gap, g), missed + m, bad + b
        failed += int(m > 0 or b > 0 or g > ck["limits"][gap_name(rerank)])
    return {gap_name(rerank): gap, "missed": missed, "bad_ids": bad}, failed


def within(numbers: dict, limits: dict) -> bool:
    return all(numbers[n] <= limits[n] for n in limits)


def recall_at_k(base: np.ndarray, queries: np.ndarray, ids: np.ndarray,
                k: int, chunk: int = 1 << 20) -> float:
    """recall@k of `ids` against brute force over the whole corpus, on the
    device in row chunks (f32 at full precision; a fact, not a check)."""
    import jax
    import jax.numpy as jnp

    chunk = min(chunk, len(base))

    @jax.jit
    def part(x, valid, q):
        x = x.astype(jnp.float32)
        qx = jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST)
        d = jnp.sum(q * q, 1)[:, None] - 2.0 * qx + jnp.sum(x * x, 1)[None]
        d = jnp.where(jnp.arange(x.shape[0])[None] < valid, d, jnp.inf)
        neg, idx = jax.lax.top_k(-d, k)
        return -neg, idx

    q = jnp.asarray(queries, jnp.float32)
    best_d = np.full((len(q), 0), np.inf, np.float32)
    best_i = np.zeros((len(q), 0), np.int64)
    for s in range(0, len(base), chunk):
        x = base[s:s + chunk]
        valid = len(x)
        if valid < chunk:  # one shape for every chunk
            x = np.concatenate([x, np.zeros((chunk - valid,) + x.shape[1:],
                                            x.dtype)])
        d, i = part(jnp.asarray(x), valid, q)
        best_d = np.concatenate([best_d, np.asarray(d)], 1)
        best_i = np.concatenate([best_i, np.asarray(i) + s], 1)
        sel = np.argsort(best_d, axis=1, kind="stable")[:, :k]
        best_d = np.take_along_axis(best_d, sel, 1)
        best_i = np.take_along_axis(best_i, sel, 1)
    hits = sum(len(set(a.tolist()) & set(b.tolist()))
               for a, b in zip(ids, best_i))
    return hits / best_i.size
