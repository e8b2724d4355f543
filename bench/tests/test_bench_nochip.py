"""Without a TPU the benchmark exits with an error and prints no result,
also from a directory that holds only BENCHMARK.json and ``bench/``."""
import os
import shutil
import subprocess
import sys

from bench import harness


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dsl7b-2l.train",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_cpu_only_exits_without_result():
    p = _run(harness.ROOT)
    _no_result(p)
    assert p.returncode == 3, p.stderr[-2000:]


def test_benchmark_files_alone_exit_without_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    _no_result(_run(tmp_path))
