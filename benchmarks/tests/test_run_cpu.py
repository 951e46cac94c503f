"""The command refuses to measure without a chip, and outside a checkout."""

import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
ARGS = ["--workload", "resnet50_b128_train", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), *ARGS],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_result_without_a_tpu():
    p = _run(CHECKOUT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout


def test_no_result_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout


def test_unknown_device_kind_is_an_error():
    sys.path.insert(0, BENCH)
    from lib import peaks
    import pytest
    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_a_cell_with_provisional_limits_is_refused():
    """resnet50_dp4_train has its files written and its limits unread: the
    command gives no result for it, whatever it runs on."""
    p = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--workload",
         "resnet50_dp4_train", *ARGS[2:]], cwd=CHECKOUT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and "provisional" in p.stderr
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout
