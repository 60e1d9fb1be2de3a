"""The command the driver runs must refuse what it cannot measure, and
print no result when it does."""

import os
import shutil
import subprocess
import sys

from bench_tiny import make_root
from benchmark import manifest

ARGS = ["--workload", "rw_backlog", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_the_command_refuses_the_cpu_and_prints_no_result(tmp_path):
    # a checkout of its own, cut to a tiny size: the command starts the
    # state DB's child before it asks jax for the device
    root = make_root(tmp_path)
    os.symlink(os.path.join(manifest.ROOT, "fabric_tpu"),
               os.path.join(root, "fabric_tpu"))
    res = subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300, cwd=root)
    assert res.returncode != 0
    assert "needs platform 'tpu'" in res.stderr
    assert '"correct"' not in res.stdout and '"metrics"' not in res.stdout
    # and it took its children and its work directory with it
    assert not os.listdir(os.path.join(root, "benchmark", ".work"))


def test_the_benchmark_alone_is_not_enough(tmp_path):
    """In a directory that holds only ``BENCHMARK.json`` and the files
    under ``paths`` there is no program to measure."""
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(manifest.ROOT, "benchmark"), tmp_path / "benchmark",
        ignore=shutil.ignore_patterns(".cache", ".work", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    res = subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS], env=env,
        capture_output=True, text=True, timeout=300, cwd=tmp_path)
    assert res.returncode != 0
    assert "fabric_tpu" in res.stderr
    assert '"correct"' not in res.stdout


def test_an_unknown_cell_is_refused():
    res = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, "benchmark", "run.py"),
         "--workload", "no_such_cell", *ARGS[2:]],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300, cwd=manifest.ROOT)
    assert res.returncode != 0 and '"correct"' not in res.stdout
