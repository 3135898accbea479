"""Paper Fig. 14: near-linear QPS scaling with #DPUs (= devices).

Runs in one process over retrieval meshes of the first 1, 2, 4 and 8
devices (`make_retrieval_mesh(n)`, as many as the backend has), so a
process that holds the chip never starts a child that needs it.  On the
CPU fake-device mesh the devices share the host's cores, so wall-QPS
saturates; the *scheduled-load-per-device* column is the scaling signal,
matching the paper's aggregated-bandwidth argument."""

from __future__ import annotations

import time

import jax

from benchmarks.common import emit, small_system
from repro.launch.mesh import make_retrieval_mesh


def run():
    loads = []
    for ndev in (1, 2, 4, 8):
        if ndev > len(jax.devices()):
            break
        _, stream, eng = small_system(
            n=15000, c=48, mesh=make_retrieval_mesh(ndev)
        )
        qs = stream.queries(64, seed=2)
        eng.search(qs, nprobe=8, k=10)  # warm
        t0 = time.perf_counter()
        eng.search(qs, nprobe=8, k=10)
        qps = len(qs) / (time.perf_counter() - t0)
        sch, _, _ = eng.schedule_batch(qs, 8)
        max_load = float(sch.dev_load.max())
        loads.append((ndev, max_load))
        emit(
            f"fig14_scaling_dev{ndev}",
            1e6 / qps,
            f"qps={qps:.1f};max_dev_load={max_load:.0f};"
            f"mean_dev_load={float(sch.dev_load.mean()):.0f}",
        )
    if len(loads) >= 2:
        # per-device load should scale ~1/ndev (aggregated-bandwidth claim)
        n0, l0 = loads[0]
        n1, l1 = loads[-1]
        ratio = (l0 / l1) / (n1 / n0)
        emit("fig14_load_scaling_efficiency", 0.0, f"efficiency={ratio:.2f}")


if __name__ == "__main__":
    run()
