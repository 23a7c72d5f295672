"""Without a TPU, and in a checkout that holds only the benchmark, the
harness exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

from bench.tests.small import BENCH

ARGS = ["bench/run.py", "--workload", "qwen05b-chat-poisson", "--seed",
        str(2**31 + 9), "--seconds", "1", "--trace", "0"]


def run_in(root, cache):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache))
    return subprocess.run([sys.executable] + ARGS, cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)


def assert_no_result(p):
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    p = run_in(BENCH.parent, tmp_path)
    assert_no_result(p)
    assert "needs a TPU" in p.stderr


def test_only_the_benchmark_files(tmp_path):
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert_no_result(run_in(tmp_path, tmp_path / "cache"))
