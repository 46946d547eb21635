"""The peaks table and the refusal to run without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, peaks


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_raises():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "minitron-4b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _claims_result(stdout):
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "correct" in json.loads(lines[-1])
    except ValueError:
        return False


def test_run_without_tpu_exits_nonzero():
    p = _run(harness.ROOT, {})
    assert p.returncode != 0
    assert not _claims_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_run_with_only_the_benchmark_exits_nonzero(tmp_path):
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    env = {"PYTHONPATH": ""}
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert not _claims_result(p.stdout)
