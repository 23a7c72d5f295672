"""Pipeline-parallel training example: GPipe microbatch schedule over a
`pipe` mesh axis (requires >= 2 devices; run under
XLA_FLAGS=--xla_force_host_platform_device_count=4 on CPU).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/pretrain_pp.py
"""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.parallel.pipeline import bubble_fraction, pipeline_forward, \
    split_stages


def main():
    n_dev = len(jax.devices())
    stages = 4 if n_dev >= 4 else max(n_dev, 1)
    if stages < 2:
        print("need >=2 devices; set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=4")
        return
    mesh = make_mesh((stages,), ("pipe",))
    d, layers, n_micro, mb = 64, 8, 8, 4
    w = jax.random.normal(jax.random.PRNGKey(0), (layers, d, d),
                          jnp.float32) * 0.2

    def stage_fn(p, x):
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        y, _ = jax.lax.scan(body, x, p)
        return y

    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d),
                          jnp.float32)
    fn = pipeline_forward(mesh, "pipe", stage_fn, n_micro=n_micro)
    with mesh:
        out = jax.jit(fn)(split_stages(w, stages), x)
    # sequential check
    ref = x
    for l in range(layers):
        ref = jnp.tanh(ref @ w[l])
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"stages={stages} micro={n_micro} "
          f"bubble={bubble_fraction(n_micro, stages):.2%} "
          f"max|pp - sequential|={err:.2e}")
    assert err < 1e-5
    print("OK: pipeline schedule matches sequential execution.")


if __name__ == "__main__":
    main()
