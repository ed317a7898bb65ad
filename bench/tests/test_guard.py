"""No TPU, no result: the benchmark exits non-zero and prints nothing on
standard output; a chip the peaks table lacks is an error."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

from conftest import ROOT


def run_bench(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_cpu_run_exits_nonzero_without_a_line():
    r = run_bench(ROOT, "--workload", "internlm2-1.8b.chat-decode",
                  "--seed", "3", "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_bare_checkout_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and bench/: no program."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run_bench(str(tmp_path), "--workload", "qwen3-1.7b.zero1-dp4",
                  "--seed", "3", "--seconds", "1", "--trace", "1")
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.NoDevice):
        harness.peaks("TPU v9 imaginary")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
