"""Where JAX keeps its persistent compilation cache.

Entry points (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) call :func:`use_compile_cache`
before their first compile, so a second run of the same programs on the
same machine loads them instead of compiling again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The fixed cache path inside the checkout (git-ignored). The path is part
#: of what the cache is keyed on, so it must not move between runs.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the one directory used:
    JAX reads it itself and nothing else is set. Otherwise the cache sits
    at :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
