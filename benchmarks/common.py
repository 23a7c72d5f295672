"""Shared benchmark utilities. All benchmarks print ``name,us_per_call,derived``
CSV rows (harness contract) and run at CPU smoke scale unless they read
dry-run artifacts (full scale, analytic). BENCH_*.json artifacts carry a
``meta`` stamp (:func:`bench_meta`) so the perf trajectory stays
comparable across machines and jax versions."""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable, Dict

import jax
import numpy as np

#: Version of the BENCH_*.json artifact envelope: {"meta": ..., results}.
#: Bump when the envelope (not a benchmark's own rows) changes shape.
BENCH_SCHEMA_VERSION = 1


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5,
            **kwargs) -> float:
    """Mean wall-time per call in microseconds (block_until_ready fenced)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kwargs))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        ts.append(time.perf_counter() - t0)
    return float(np.mean(ts)) * 1e6


def emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}")


def bench_meta() -> Dict[str, Any]:
    """Environment stamp for BENCH_*.json artifacts: schema version, jax
    version, backend, device kind/count, and whether the CPU "devices"
    are forced host devices (``--xla_force_host_platform_device_count``
    makes an 8-device CPU mesh out of one socket — numbers from such a
    run must never be compared against real-accelerator rows)."""
    devs = jax.devices()
    return {
        "bench_schema_version": BENCH_SCHEMA_VERSION,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "none",
        "device_count": jax.device_count(),
        "forced_host_devices":
            "--xla_force_host_platform_device_count"
            in os.environ.get("XLA_FLAGS", ""),
    }


def write_bench_json(path: str, results: Any) -> None:
    """Write a BENCH_*.json artifact as ``{"meta": bench_meta(),
    "results": results}`` — every benchmark's writer goes through here so
    no artifact ships unstamped."""
    with open(path, "w") as f:
        json.dump({"meta": bench_meta(), "results": results}, f, indent=2)


def run_model_parallel_rows(module: str, measure: Callable[[int], dict],
                            degrees, forced_devices: int):
    """Yield (tp, row_dict) for each TP degree; a failed degree raises.

    On the CPU backend each degree re-runs ``module`` (a ``python -m``-able
    benchmark) in a child process with the host device count forced:
    ``--xla_force_host_platform_device_count`` must land before the jax
    backend initializes, and forcing it here would distort this process's
    single-device rows. Each child prints one JSON line (its module's
    ``--model-parallel`` branch). On any other backend this process
    already holds the chips and a child could not reach them, so
    ``measure(tp)`` runs here over ``jax.devices()``; degrees wider than
    the device count are left out."""
    if jax.default_backend() != "cpu":
        for tp in degrees:
            if tp <= jax.device_count():
                yield tp, measure(tp)
        return
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{forced_devices}").strip()
    for tp in degrees:
        proc = subprocess.run(
            [sys.executable, "-m", module, "--model-parallel", str(tp)],
            capture_output=True, text=True, env=env)
        lines = proc.stdout.splitlines()
        if proc.returncode != 0 or not lines:
            raise RuntimeError(f"{module} tp{tp} child failed "
                               f"(exit {proc.returncode}):\n"
                               f"{proc.stderr[-2000:]}")
        yield tp, json.loads(lines[-1])
