"""From a configuration file to a warmed serving engine.

The configuration file (``bench/configs/<name>.json``) holds the model's
published ``config.json`` keys as they are run, an optional ``family``
(bench/families/, ``dense`` where it is left out) and an ``engine``
section (block size, prefill chunk, ``max_batch``, KV dtype, chips); the
KV pool is always fitted to the chips (:func:`fit_pool`). The weights are
made here, from the seed, not by the program: :func:`make_weights` draws
every leaf of the family's layout on the device in one jitted call, and
:func:`check_layout` checks that the tree is the one the program's model
reads.

On more than one chip the engine runs the program's tensor-parallel path:
a (data 1, model n) mesh over the cell's chips (:func:`make_mesh`), every
weight drawn straight into the sharding the engine gives it
(:func:`param_shardings`), so no chip ever holds the whole tree.
"""
from __future__ import annotations

import contextlib
import math
from typing import Callable, Dict, List, Tuple

import numpy as np

from bench import families

VOCAB_PAD = 512
POOL_SHARE = 0.8    # of the device's memory, for the pool and the steps' plans


def padded_vocab(v: int) -> int:
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def _leaves(tree, prefix=()):
    for key in sorted(tree):
        val = tree[key]
        if isinstance(val, dict):
            yield from _leaves(val, prefix + (key,))
        else:
            yield prefix + (key,), val


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def make_weights(conf, seed: int, shardings=None):
    """Every weight of the family's layout from one jitted call: on the
    default device, or each leaf straight into its sharding in
    ``shardings`` (a tree like the weights'). The draw is the same
    whatever the sharding.

    Leaf ``i``, in the sorted order of the paths, is drawn from the seed's
    key folded with ``i``, by its init: ``ones``, ``bias`` (normal x 0.1),
    ``embed`` (normal over sqrt(d), padded vocabulary rows zero), ``head``
    (likewise, for an untied (d, vocab) projection), an int (the fan-in a
    normal draw is scaled by), or a function of (key, shape) that returns
    float32. It is cast to its dtype, bf16 unless the layout names
    another."""
    import jax
    import jax.numpy as jnp

    lay = families.load(conf).layout(conf)
    vocab = conf["vocab_size"]

    def draw(key):
        out: Dict = {}
        for i, (path, (shp, init, *dtype)) in enumerate(_leaves(lay)):
            k = jax.random.fold_in(key, i)
            if callable(init):
                a = init(k, shp)
            elif init == "ones":
                a = jnp.ones(shp, jnp.float32)
            elif init == "bias":
                a = 0.1 * jax.random.normal(k, shp, jnp.float32)
            elif init == "embed":
                a = jax.random.normal(k, shp, jnp.float32) / math.sqrt(shp[1])
                a = jnp.where(jnp.arange(shp[0])[:, None] < vocab, a, 0.0)
            elif init == "head":
                a = jax.random.normal(k, shp, jnp.float32) / math.sqrt(shp[0])
                a = jnp.where(jnp.arange(shp[1])[None, :] < vocab, a, 0.0)
            else:
                a = jax.random.normal(k, shp, jnp.float32) / math.sqrt(init)
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = a.astype(dtype[0] if dtype else jnp.bfloat16)
        return out

    return jax.jit(draw, out_shardings=shardings)(seed_key(seed))


def make_mesh(devices):
    """The serving mesh over the cell's own chips: the axes of the
    program's ``launch.mesh.make_local_mesh(model=n, data=1)``."""
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh((1, len(devices)), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)


def param_shardings(cfg, mesh):
    """The sharding the engine gives each weight under ``mesh``, from the
    program's own resolver (``Engine.__init__`` places its parameters
    with the same call)."""
    from repro.models.lm import LM
    from repro.parallel.sharding import logical_by_path_of, \
        make_serving_ctx, state_shardings
    ctx = make_serving_ctx(cfg, mesh)
    lm = LM(cfg, ctx=ctx)
    return state_shardings(ctx, lm.abstract_params(),
                           logical_by_path_of(lm.param_specs()),
                           component="params")


def bytes_per_chip(tree) -> Dict:
    """Bytes of ``tree``'s arrays that each device holds."""
    import jax
    out: Dict = {}
    for a in jax.tree.leaves(tree):
        for sh in a.addressable_shards:
            out[sh.device] = out.get(sh.device, 0) + sh.data.nbytes
    return out


def check_layout(cfg, params) -> None:
    """The weights must have exactly the tree the program's model reads."""
    import jax
    from repro.models.lm import LM

    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype),
                                  LM(cfg).abstract_params())
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError(f"weight layout differs from the model's: "
                         f"{want} != {got}")


def _home(mesh):
    """Where the engine makes its device state: the default device, or
    under a mesh the host (see :func:`build_engine`)."""
    import jax
    return contextlib.nullcontext() if mesh is None else \
        jax.default_device(jax.devices("cpu")[0])


def build_engine(cfg, params, es, n_blocks: int, mesh=None):
    """The program's ``Engine`` over ``params``, on ``mesh`` where the cell
    has more than one chip.

    Under a mesh the engine makes its pool whole on the default device and
    only then spreads it over the mesh (``serving/cache.init_state``): a
    pool fitted to four chips would not fit on one beside its weights. So
    the engine is built with the host as the default device, and the pool
    goes from there straight to its shards."""
    from repro.serving.engine import Engine

    with _home(mesh):
        return Engine(cfg, params, max_batch=es["max_batch"],
                      n_blocks=n_blocks, block_size=es["block_size"],
                      kv_quant="int8" if es["kv_dtype"] == "int8" else "none",
                      prefill_chunk=es["prefill_chunk"], mesh=mesh)


def device_state(eng):
    """Every device array the engine holds beside its weights: the KV pool
    and the per-slot recurrent state (``{}`` for a model with none)."""
    return eng.kv.state, eng._ssm_states


def free_state(eng) -> None:
    """Delete the engine's device state, as before the reference runs."""
    import jax
    for a in jax.tree.leaves(device_state(eng)):
        a.delete()


def renew_state(eng) -> None:
    """Give the engine fresh device state, made as its own start made it,
    in the same shapes and shardings: its steps do not compile again."""
    from repro.serving.cache import init_state
    with _home(eng.mesh):
        eng.kv.state = init_state(eng.kv_cfg, sharding=eng.kv.sharding)
        eng._ssm_states = eng._init_ssm_states()


def chip_bytes(tree) -> int:
    """Bytes of ``tree``'s arrays that one chip holds, by their shardings."""
    import jax
    return sum(math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))


def buckets(max_tokens: int, block: int) -> List[int]:
    """Power-of-two block-table widths up to the one ``max_tokens`` needs:
    every width a decode or chunk step of the cell can be given."""
    top = 1 << (-(-max_tokens // block) - 1).bit_length()
    return [1 << i for i in range(top.bit_length())]


def _lower(eng, kind: str, n_blocks: int, width: int, sharding):
    """Compile ``eng``'s decode or chunk step over a pool of ``n_blocks``
    blocks: the weights, the pool and the recurrent state as the engine
    places them, every other argument by ``sharding``. Nothing is
    allocated."""
    import jax
    import jax.numpy as jnp

    def sds(shape_, dtype, sh=sharding):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=sh)

    def like(tree):
        return jax.tree.map(lambda a: sds(a.shape, a.dtype, a.sharding), tree)

    params, ssm = like(eng.params), like(eng._ssm_states)
    kv = {k: sds((a.shape[0], n_blocks) + tuple(a.shape[2:]), a.dtype,
                 a.sharding) for k, a in eng.kv.state.items()}
    i32, per = jnp.int32, eng.model.n_periods
    if kind == "decode":
        b = eng.max_batch
        return eng._fused_step.lower(
            params, kv, ssm, sds((b,), i32), sds((b,), i32),
            sds((b, width), i32), sds((b,), jnp.bool_),
            sds((per, b), jnp.bool_)).compile()
    cn = eng.prefill_chunk
    return eng._chunk_step.lower(
        params, kv, ssm, sds((1, cn), i32), sds((), i32), sds((), i32),
        sds((1, width), i32), sds((), i32), sds((per, 1), jnp.bool_)).compile()


def plan_bytes(compiled) -> int:
    """Device bytes a compiled step plans for: arguments, outputs and
    temporaries, less the donated buffers its outputs reuse."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def device_limit(devices) -> int:
    """The least memory of the cell's chips."""
    return min(d.memory_stats()["bytes_limit"] for d in devices)


def fit_pool(cfg, params, es, width: int, devices, log: Callable,
             mesh=None) -> Tuple[int, dict]:
    """The most blocks whose decode and chunk steps at the widest table,
    beside the live pool and the resident recurrent state, fit
    :data:`POOL_SHARE` of each chip's memory.

    The chip with the least memory sets the budget. The steps are compiled
    with the weights, the pool and the recurrent state as the engine
    places them, so their plans (``memory_analysis``), a block's bytes and
    the state's are what one chip holds. A plan holds the copies of the
    pool and the state that warm-up hands each step, beside the live ones
    counted here. The plans grow linearly with the pool, so two small
    probes give the line; the pool found is compiled once more to confirm
    it. Every program here is the same in every run, so the persistent
    cache holds them after the first."""
    from jax.sharding import NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    limit = device_limit(devices)
    budget = POOL_SHARE * limit
    probe = build_engine(cfg, params, es, 2 * es["max_batch"], mesh)
    per_block = chip_bytes(probe.kv.state) // probe.kv_cfg.n_blocks
    state = chip_bytes(probe._ssm_states)
    shard = SingleDeviceSharding(devices[0]) if mesh is None else \
        NamedSharding(mesh, PartitionSpec())

    def need(n):
        plans = {k: plan_bytes(_lower(probe, k, n, width, shard))
                 for k in ("decode", "chunk")}
        return max(plans.values()) + per_block * n + state, plans

    try:
        lo, hi = 256, 512
        (a, _), (b, _) = need(lo), need(hi)
        slope = (b - a) / (hi - lo)
        n = int((budget - (a - slope * lo)) // slope) // 64 * 64
        while n > 0:
            total, plans = need(n)
            if total <= budget:
                break
            n -= 64
    finally:
        free_state(probe)
    if n <= 0:
        raise RuntimeError(f"no pool fits {devices}")
    info = {"blocks": n, "pool_bytes": per_block * n, "state_bytes": state,
            "decode_plan_bytes": plans["decode"],
            "chunk_plan_bytes": plans["chunk"], "budget_bytes": int(budget),
            "device_limit_bytes": limit}
    log(f"pool: {info}")
    return n, info


def warm(eng, widths: List[int]) -> None:
    """Compile the decode and chunk steps at every table width the cell can
    use (``Engine.warmup`` compiles both at the width ``max_seq_len``
    implies)."""
    for w in widths:
        eng.warmup(w * eng.block_size)
