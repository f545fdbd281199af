"""The command refuses to run where it cannot measure: no TPU, a device
kind the peak table lacks, or a checkout without the program."""
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload", "reloc_ycsb_zipf",
         "--seed", "3000000019", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax

    from bench import harness

    fake = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(harness.CellError, match="not in bench/peaks.json"):
        harness.device_info(1, require_tpu=True)
    fake.device_kind = "TPU v5 lite"
    device, peaks = harness.device_info(1, require_tpu=True)
    assert device == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flops_per_s"] == 197e12


def test_too_few_chips_is_an_error(monkeypatch):
    import jax

    from bench import harness

    fake = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(harness.CellError, match="needs 4 chips"):
        harness.device_info(4, require_tpu=True)
