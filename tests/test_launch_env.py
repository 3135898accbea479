"""Launch-time honesty about the device: peaks, roofline columns, cache.

A roofline share is only a device metric when it was measured on a TPU
against that TPU's peaks; the compile cache lives at one fixed place.
"""

import json
import os

import pytest

from benchmarks.run import write_json
from repro.launch import env as launch_env
from repro.launch.roofline_report import PEAKS, peaks_for


def test_peaks_for_known_tpu_kind():
    flops, bw, source = peaks_for("TPU v5 lite")
    assert (flops, bw) == PEAKS["TPU v5 lite"]
    assert source == "table:TPU v5 lite"


@pytest.mark.parametrize("kind", ["cpu", "TPU v99", "", None])
def test_peaks_for_unknown_kind_raises(kind):
    with pytest.raises(ValueError, match="no peaks"):
        peaks_for(kind)


def test_peaks_for_overrides():
    assert peaks_for("cpu", 1.0, 2.0) == (1.0, 2.0, "override")
    assert peaks_for("TPU v5e", hbm_bw=2.0) == (
        PEAKS["TPU v5e"][0], 2.0, "override"
    )


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_roofline_column_only_on_tpu_rows(tmp_path, backend):
    meta = {"backend": backend, "device_kind": "x", "autotune": "off"}
    if backend == "tpu":
        meta.update(peak_flops=1.0, hbm_bw=1e9, peaks_source="table:x")
    path = str(tmp_path / "bench.json")
    write_json(path, [("scan", 1000.0, "ideal_bytes=1000000")], meta=meta)
    with open(path) as f:
        row = json.load(f)["rows"]["scan"]
    if backend == "tpu":
        assert row["roofline_frac"] == pytest.approx(1.0)
        assert row["peaks_source"] == "table:x"
    else:
        assert "roofline_frac" not in row and "peaks_source" not in row


def _isolate_env(monkeypatch):
    """Let monkeypatch restore every variable setup_env may write."""
    for key in ("XLA_FLAGS", "TF_CPP_MIN_LOG_LEVEL", "JAX_PLATFORMS"):
        if key in os.environ:
            monkeypatch.setenv(key, os.environ[key])
        else:
            monkeypatch.delenv(key, raising=False)


def test_compile_cache_defaults_to_checkout(monkeypatch):
    _isolate_env(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    applied = launch_env.setup_env()
    want = str(launch_env.DEFAULT_CACHE_DIR)
    assert applied["JAX_COMPILATION_CACHE_DIR"] == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert launch_env.DEFAULT_CACHE_DIR.name == ".jax_cache"
    assert (launch_env.DEFAULT_CACHE_DIR.parent / "pyproject.toml").exists()


def test_compile_cache_dir_from_environment_wins(monkeypatch, tmp_path):
    _isolate_env(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    applied = launch_env.setup_env()
    assert "JAX_COMPILATION_CACHE_DIR" not in applied
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
