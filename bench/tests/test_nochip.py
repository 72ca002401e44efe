"""Without a TPU the benchmark exits non-zero and prints no result."""
import os
import subprocess
import sys

from bench.tests.fixture import REPO


def test_run_without_a_chip_prints_nothing_and_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gpt2-117m.train-slw",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "TPU" in proc.stderr


def test_unknown_workload_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "no-such-cell",
         "--seed", "1", "--seconds", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""
