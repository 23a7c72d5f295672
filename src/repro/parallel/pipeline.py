"""Pipeline parallelism: GPipe-style microbatch schedule over a `pipe` mesh
axis using shard_map + collective_permute (the jax-native mapping of
Megatron's inter-stage P2P sends).

Design: the layer stack is split into S stages of L/S layers. Each device
ring-shifts activations with ppermute; a rolled schedule of (M + S - 1)
ticks runs microbatch m on stage s at tick m + s. Bubble fraction
(S-1)/(M+S-1) is reported so the launcher can size M.

This is exercised by tests/test_pipeline.py on host devices and by
examples/pretrain_pp.py; the production dry-run mesh keeps `pod` as a DP
axis (DeepSpeed-style deployment, paper §II-B) — PP is the Megatron-side
alternative and composes with the same Technique matrix.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_forward(mesh: Mesh, axis: str, stage_fn: Callable,
                     n_micro: int):
    """Build fwd(params_stacked, x_micro) running a GPipe pipeline.

    params_stacked: pytree with leading dim = n_stages (stage s's params
    live on pipe rank s). x_micro: (n_micro, mb, ...) activations, all
    microbatches resident on stage 0's rank (sharded spec P(axis) over the
    stacked stage dim for params; x replicated then masked per rank).
    """
    n_stages = mesh.shape[axis]

    def local(params_local, x):
        # params_local: this rank's stage params (leading 1 squeezed)
        p = jax.tree_util.tree_map(lambda a: a[0], params_local)
        rank = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        mb_shape = x.shape[1:]

        def tick(carry, t):
            buf, outputs = carry
            # stage input: rank 0 injects microbatch t; others use buf
            inject = jnp.where(t < n_micro,
                               x[jnp.clip(t, 0, n_micro - 1)],
                               jnp.zeros(mb_shape, x.dtype))
            cur = jnp.where(rank == 0, inject, buf)
            y = stage_fn(p, cur)
            # emit finished microbatch from the last stage
            out_idx = t - (n_stages - 1)
            is_out = jnp.logical_and(rank == n_stages - 1,
                                     jnp.logical_and(out_idx >= 0,
                                                     out_idx < n_micro))
            outputs = jnp.where(
                is_out,
                outputs.at[jnp.clip(out_idx, 0, n_micro - 1)].set(y),
                outputs)
            # ring-shift activations to the next stage
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outputs), None

        buf0 = jnp.zeros(mb_shape, x.dtype)
        outs0 = jnp.zeros((n_micro,) + mb_shape, x.dtype)
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(n_ticks))
        # every rank returns its outputs buffer; only the last stage's is
        # non-zero — psum_scatter-free: collapse with a max over the axis
        outputs = jax.lax.psum(outputs, axis)   # zeros elsewhere -> identity
        return outputs

    pspec = jax.tree_util.tree_map(lambda _: P(axis), {"_": 0})["_"]
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(axis), P()),
                       out_specs=P(),
                       check_vma=False)
    return fn


def split_stages(stacked_params, n_stages: int):
    """Reshape scan-stacked (L, ...) params into (S, L/S, ...)."""
    def f(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])
    return jax.tree_util.tree_map(f, stacked_params)
