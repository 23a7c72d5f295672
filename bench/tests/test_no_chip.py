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


def test_chips_unlike_the_configurations_exit_before_any_weights(tmp_path):
    """A cell that asks for another number of chips than its configuration
    is built for is refused as the cell is read: before the look for a
    chip, and so before any weight is drawn."""
    import json
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for wl in bench["workloads"]:
        if wl["name"] == ARGS[2]:
            wl["chips"] = 4
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    p = run_in(tmp_path, tmp_path / "cache")
    assert_no_result(p)
    assert "built for 1 chips, the cell asks for 4" in p.stderr
    assert "needs a TPU" not in p.stderr
