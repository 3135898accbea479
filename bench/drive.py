"""The measured window: the mix's requests through `ServingEngine.search`.

Each function serves the window on one thread, timing by the host's clock
(`time.perf_counter`), and keeps every answer for the check.  `span(name)`
brackets the harness's own host work (`generator`: waiting for or
preparing the next request; `result`: storing an answer) so that a trace
can attribute idle device time to it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Window:
    t0: float                      # perf_counter at the window's start
    elapsed_s: float               # start to the last answer
    query_index: np.ndarray        # (A,) pool row of each answered query
    dists: np.ndarray              # (A, k)
    ids: np.ndarray                # (A, k)
    calls: int                     # search() calls
    call_s: np.ndarray             # (calls,) seconds in each search() call
    call_t: np.ndarray             # (calls,) its start, s from t0
    latency_s: np.ndarray | None = None   # (A,) due -> answered
    wait_s: np.ndarray | None = None      # (A,) due -> its search() call
    late_s: np.ndarray | None = None      # generator oversleep per wake

    @property
    def answered(self) -> int:
        return int(self.query_index.shape[0])


def no_span(name):
    return contextlib.nullcontext()


def backlog(srv, pool: np.ndarray, request_queries: int, seconds: float,
            span=no_span) -> Window:
    """Requests of `request_queries` pool rows, in turn, each sent as the
    previous answer returns, until `seconds` have passed."""
    n_req = pool.shape[0] // request_queries
    out_d, out_i, rows, call_s, call_t = [], [], [], [], []
    calls = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with span("generator"):
            r = calls % n_req
            idx = np.arange(r * request_queries, (r + 1) * request_queries)
        t_call = time.perf_counter()
        d, i = srv.search(pool[idx])
        call_s.append(time.perf_counter() - t_call)
        call_t.append(t_call - t0)
        with span("result"):
            out_d.append(d)
            out_i.append(i)
            rows.append(idx)
        calls += 1
    t1 = time.perf_counter()
    return Window(t0=t0, elapsed_s=t1 - t0, query_index=np.concatenate(rows),
                  dists=np.concatenate(out_d), ids=np.concatenate(out_i),
                  calls=calls, call_s=np.asarray(call_s),
                  call_t=np.asarray(call_t))


def open_loop(srv, pool: np.ndarray, due: np.ndarray, micro_batch: int,
              span=no_span) -> Window:
    """Query j is due at `due[j]` seconds; whenever the server is free it
    takes everything due, up to `micro_batch` queries, in one call.
    Every due query is served, also those still queued at the close."""
    n = due.shape[0]
    lat = np.empty(n)
    wait = np.empty(n)
    out_d, out_i, late, call_s, call_t = [], [], [], [], []
    calls = 0
    i = 0
    t0 = time.perf_counter()
    while i < n:
        with span("generator"):
            now = time.perf_counter() - t0
            if due[i] > now:
                time.sleep(due[i] - now)
                now = time.perf_counter() - t0
                late.append(now - due[i])
            j = int(np.searchsorted(due, now, side="right"))
            j = min(max(j, i + 1), i + micro_batch)
        t_call = time.perf_counter() - t0
        d, ids = srv.search(pool[i:j])
        t_done = time.perf_counter() - t0
        call_s.append(t_done - t_call)
        call_t.append(t_call)
        with span("result"):
            lat[i:j] = t_done - due[i:j]
            wait[i:j] = t_call - due[i:j]
            out_d.append(d)
            out_i.append(ids)
        calls += 1
        i = j
    t1 = time.perf_counter()
    return Window(t0=t0, elapsed_s=t1 - t0, query_index=np.arange(n),
                  dists=np.concatenate(out_d), ids=np.concatenate(out_i),
                  calls=calls, call_s=np.asarray(call_s),
                  call_t=np.asarray(call_t), latency_s=lat,
                  wait_s=wait,
                  late_s=np.asarray(late))
