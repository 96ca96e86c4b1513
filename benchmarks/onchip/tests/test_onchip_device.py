"""The command refuses to measure anything but a chip it has peaks for."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from harness import peaks  # noqa: E402

ROOT = HERE.parents[1]
ARGS = ["--workload", "mamba2-batch", "--seed", str(2 ** 31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd: Path, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/onchip/run.py"] + ARGS, cwd=cwd,
        env=env, capture_output=True, text=True, timeout=timeout)


def test_off_the_chip_the_run_exits_nonzero_with_no_result():
    p = _run(ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_unknown_device_kind_has_no_peaks():
    assert peaks.peak_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak_for("TPU v99")


def test_the_benchmark_alone_cannot_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no system to measure."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
