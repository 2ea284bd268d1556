"""The benchmark refuses to run anywhere but on a TPU."""

import os
import shutil
import subprocess
import sys

import pytest

import harness
from conftest import BENCH, ROOT


def test_device_info_refuses_the_cpu():
    with pytest.raises(harness.NoDevice, match="no TPU"):
        harness.device_info(1)


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "resnet18-224.sweep64", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_command_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode == 3
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_command_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), str(tmp_path))
    shutil.copytree(BENCH, str(tmp_path / "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout == ""
