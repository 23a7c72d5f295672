"""Mesh factories.

``make_production_mesh`` is the dry-run target: one TPU v5e pod is a 16x16
torus (256 chips); multi-pod adds a leading "pod" axis over DCN (2 pods =
512 chips). Functions, not module constants — importing this module never
touches jax device state.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType


def ensure_host_devices(n: int) -> None:
    """Make the CPU backend expose at least ``n`` devices (test / smoke
    meshes, e.g. ``--model-parallel 8`` on a laptop). Sets
    ``--xla_force_host_platform_device_count=n`` in XLA_FLAGS — raising an
    existing smaller value in place — which only takes effect if the
    backend has not initialized yet; raises with the manual incantation
    when it is too late (some import already touched jax device state)."""
    if n <= 1:
        return
    flag = "--xla_force_host_platform_device_count"
    tokens = os.environ.get("XLA_FLAGS", "").split()
    for t in tokens:                      # never LOWER an explicit count
        if t.startswith(flag + "="):
            try:
                n = max(n, int(t.split("=", 1)[1]))
            except ValueError:
                pass
    kept = [t for t in tokens if not t.startswith(flag)]
    os.environ["XLA_FLAGS"] = " ".join(kept + [f"{flag}={n}"])
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices but the jax backend initialized with "
            f"{len(jax.devices())}; relaunch with XLA_FLAGS={flag}={n}")


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Every mesh in the repo comes from here, with ``Auto`` axes: the
    sharding code states layouts through ``with_sharding_constraint`` and
    leaves the rest to GSPMD. ``jax.make_mesh`` defaults to ``Explicit``
    axes (JAX >= 0.8), under which those constraints are refused."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1, data: Optional[int] = None):
    """Mesh over whatever devices exist (tests / CPU smoke runs). The
    serving engine uses ``make_local_mesh(model=N, data=1)``: a pure
    model-parallel mesh — the batch is host-global, only tensors shard."""
    n = len(jax.devices())
    data = data or (n // model)
    assert data * model <= n, (data, model, n)
    return make_mesh((data, model), ("data", "model"))


def mesh_layout(mesh) -> dict:
    return {"shape": dict(mesh.shape), "axes": list(mesh.axis_names),
            "devices": int(np.prod(list(mesh.shape.values())))}
