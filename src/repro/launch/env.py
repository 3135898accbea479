"""Process-environment setup for launchers (serve / benchmarks / dryruns).

XLA reads most of its knobs from environment variables at backend
initialization, so they only take effect if set BEFORE the first
`import jax` touches a device.  Launchers therefore call `setup_env()`
at the very top of `main()` (all their jax imports are deferred into the
function body for exactly this reason) and only then build the mesh.

Three rules keep this safe everywhere the repo runs:

  * never clobber: every variable is set with `setdefault`, so CI's
    pinned `JAX_PLATFORMS=cpu` / `--xla_force_host_platform_device_count=8`
    and any operator override win over our defaults;
  * stay honest about the platform: `requested` only pins `JAX_PLATFORMS`
    when the caller asked for a specific one — the default lets jax pick
    the best available backend, and `describe_env()` reports what actually
    got initialized (backend + device kind), which the benchmark harness
    stamps onto every emitted row.  A caller that must run on an
    accelerator passes `platform` (e.g. "tpu"): JAX then fails at start-up
    instead of carrying on on the CPU;
  * one compile cache: JAX's persistent compilation cache lives where
    `JAX_COMPILATION_CACHE_DIR` says when it is set, else at the fixed
    path `<checkout>/.jax_cache` -- never a temp, pid- or time-based path,
    since the path is part of the cache's key.

The per-platform defaults follow the tuning guides (see SNIPPETS.md 1 & 3):
GPU gets the latency-hiding scheduler + async collectives and a capped
allocator so the serving process coexists with the host planner's memory;
CPU fakes a multi-device mesh (the DPU-rank stand-in used by every test
and bench) when no device count was pinned; TPU needs no flags — the
defaults are already the tuned path.
"""

from __future__ import annotations

import os
from pathlib import Path

# persistent compile cache used when JAX_COMPILATION_CACHE_DIR is unset:
# a fixed path inside the checkout (listed in .gitignore)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# fake-device count used when the caller pinned nothing: matches the CI
# mesh so locally-run benches hit the same shard shapes CI publishes
DEFAULT_HOST_DEVICES = 8

GPU_XLA_FLAGS = (
    "--xla_gpu_enable_latency_hiding_scheduler=true "
    "--xla_gpu_enable_highest_priority_async_stream=true "
    "--xla_gpu_triton_gemm_any=True"
)


def setup_env(
    platform: str | None = None,
    host_devices: int | None = None,
) -> dict[str, str]:
    """Set jax/XLA env defaults; returns the variables actually applied.

    Must run before jax initializes a backend.  `platform` pins
    `JAX_PLATFORMS` ("cpu" | "gpu" | "tpu"); None lets jax auto-select.
    `host_devices` sizes the fake CPU device mesh (None = keep a preset
    `--xla_force_host_platform_device_count`, else default 8).
    Everything goes through `setdefault`-style merging: a variable the
    user (or CI) already exported is never overwritten.
    """
    applied: dict[str, str] = {}

    def setdefault(key: str, value: str) -> None:
        if key not in os.environ:
            os.environ[key] = value
            applied[key] = value

    if platform:
        setdefault("JAX_PLATFORMS", platform)
    plat = os.environ.get("JAX_PLATFORMS", platform or "")

    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        n = host_devices if host_devices is not None else DEFAULT_HOST_DEVICES
        flags = f"{flags} --xla_force_host_platform_device_count={n}".strip()
        os.environ["XLA_FLAGS"] = flags
        applied["XLA_FLAGS"] = flags
    if plat.startswith("gpu") or plat.startswith("cuda"):
        if "--xla_gpu_enable_latency_hiding_scheduler" not in flags:
            flags = f"{flags} {GPU_XLA_FLAGS}".strip()
            os.environ["XLA_FLAGS"] = flags
            applied["XLA_FLAGS"] = flags
        # cap the preallocation so the host-side planner (numpy) and the
        # device arrays share the box without the allocator starving either
        setdefault("XLA_PYTHON_CLIENT_MEM_FRACTION", "0.85")
    setdefault("TF_CPP_MIN_LOG_LEVEL", "2")  # silence C++ backend chatter
    setdefault("JAX_COMPILATION_CACHE_DIR", str(DEFAULT_CACHE_DIR))
    return applied


def describe_env() -> dict:
    """Backend + device facts for stamping onto reports (initializes jax)."""
    import jax

    dev = jax.devices()[0]
    return {
        "backend": jax.default_backend(),
        "device_kind": dev.device_kind,
        "n_devices": jax.device_count(),
        "jax_platforms": os.environ.get("JAX_PLATFORMS", ""),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }
