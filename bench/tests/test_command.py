"""The whole command on the CPU: it finds no TPU, exits non-zero and
prints no result, and so no device metric."""

import os
import subprocess
import sys

import checkout


def _run(root, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_run_exits_nonzero_without_a_result(tmp_path):
    root = checkout.make(tmp_path)
    p = _run(root, f"{checkout.TINY}.tiny_backlog")
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout
    assert "TPU" in p.stderr


def test_no_program_in_the_checkout_exits_nonzero(tmp_path):
    root = checkout.make(tmp_path)
    (root / "src").unlink()
    p = _run(root, f"{checkout.TINY}.tiny_backlog")
    assert p.returncode != 0 and '"metrics"' not in p.stdout
