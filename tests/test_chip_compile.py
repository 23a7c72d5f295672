"""Compile the serving path for a described TPU v5e, with no chip attached.

The TPU compiler is installed even where no chip is, and it refuses what
the chip would refuse: a Pallas block that breaks Mosaic's tiling rule, a
program that does not fit the chip's memory. These tests compile the paged
attention kernel at qwen1.5-0.5b's published widths for every window
``chip_smoke.py`` runs, the fused decode step at the smoke's pool, and the
decode and chunk steps at the benchmark cells' shapes, where they check
that no step moves pool-sized data. Nothing runs, so they say nothing
about results or speed.

The topology is described inside a module fixture (only one process at a
time may load the TPU library, and pytest workers import every test file).
"""
import functools
import json
import math
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# the described chip's HBM as its compiler counts it ("Used X of 15.75G")
V5E_HBM = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache altogether
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def on_tpu(monkeypatch):
    """Make the code that asks ``jax.default_backend()`` (the engine's
    choice of paged read, the kernels' interpret default) take its TPU
    branch, so the compile lowers the Pallas kernel itself."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("t", [1, chip_smoke.CHUNK,
                               chip_smoke.SPEC_DEPTH + 1])
def test_paged_kernel_compiles_for_v5e(one_chip, kv, t):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels import flash_decode as fd

    cfg = get_config(chip_smoke.ARCH)
    b, bs, n_blocks = chip_smoke.MAX_BATCH, chip_smoke.BLOCK, 1024
    mb = 32
    store = jnp.int8 if kv == "int8" else jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the serving cache's stacked pool, heads folded into the lane axis
    rows = (cfg.n_layers, n_blocks, bs)
    pool = rows + (cfg.n_kv_heads * cfg.head_dim,)
    scale = sds(rows + (cfg.n_kv_heads,), jnp.float32) if kv == "int8" \
        else None

    def read(q, k, v, layer, table, lengths, ks, vs):
        return fd.paged_flash_prefix_partial(
            q, k, v, layer, table, lengths, k_scale=ks, v_scale=vs,
            impl="pallas", interpret=False)

    compiled = jax.jit(read).lower(
        sds((b, t, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
        sds(pool, store), sds(pool, store), sds((), jnp.int32),
        sds((b, mb), jnp.int32), sds((b,), jnp.int32), scale,
        scale).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_decode_step_fits_v5e(one_chip, on_tpu, monkeypatch):
    """The decode step at published widths, over the largest pool the
    smoke tries, holds the Pallas kernel and fits the chip's memory."""
    import jax
    from repro.configs import get_config
    from repro.models.lm import LM
    from repro.serving import cache as C
    from repro.serving.engine import Engine

    # the engine's pool and weights as shapes only: nothing is allocated
    real_init = C.init_state
    monkeypatch.setattr(C, "init_state", lambda cfg, dtype=None, sharding=None:
                        jax.eval_shape(lambda: real_init(cfg)))
    cfg = get_config(chip_smoke.ARCH)
    eng = Engine(cfg, LM(cfg).abstract_params(),
                 max_batch=chip_smoke.MAX_BATCH, n_blocks=16,
                 block_size=chip_smoke.BLOCK,
                 prefill_chunk=chip_smoke.CHUNK)
    assert eng._paged_impl == "pallas"
    compiled = chip_smoke.compile_decode_step(
        eng, chip_smoke.POOL_BLOCKS[0], one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    need = chip_smoke.step_bytes(compiled)
    assert need < V5E_HBM, (need, compiled.memory_analysis())


@pytest.mark.parametrize("kernel", ["paged_decode", "paged_prefix"])
def test_paged_kernel_lowers_with_its_name(one_chip, kernel):
    """Each paged read carries its own name into the TPU custom call, so
    the device trace says which of the two ran; the call stays a
    ``tpu_custom_call``, which is what the benchmark's kernel time
    matches."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import flash_decode as fd

    b, t, h, kv, d, bs, n_blocks, mb = 2, 4, 8, 2, 64, 16, 32, 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((3, n_blocks, bs, kv * d), jnp.bfloat16)
    layer = sds((), jnp.int32)
    table, lengths = sds((b, mb), jnp.int32), sds((b,), jnp.int32)
    if kernel == "paged_decode":
        read, q = fd.paged_flash_decode_partial, sds((b, h, d), jnp.bfloat16)
    else:
        read = fd.paged_flash_prefix_partial
        q = sds((b, t, h, d), jnp.bfloat16)
    fn = functools.partial(read, impl="pallas", interpret=False)
    text = jax.jit(fn).lower(q, pool, pool, layer, table,
                             lengths).as_text()
    assert f'kernel_name = "{kernel}"' in text
    assert "@tpu_custom_call" in text


# the widest block table a benchmark cell's step is given: 256 blocks of
# 16 tokens, a 4,096-token context
CELL_WIDTH = 256
# an instruction of the compiled text: name, result type and dims, opcode
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \w+\[([\d,]*)\]\S*\s+"
                    r"([\w\-]+)\(")
_MOVE = re.compile(r"(copy|dynamic[-_]slice)([._\-]|$)")


def pool_moves(text: str, n_blocks: int, layer_elems: int):
    """Copies and dynamic slices (by name or opcode, fused or not) whose
    result spans the pool's block axis and holds at least one layer's
    pool: a whole-pool re-layout or a per-layer slice, in any layout or
    folding."""
    found = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dims, op = m.groups()
        dims_ = [int(x) for x in dims.split(",")] if dims else []
        if (n_blocks in dims_ and math.prod(dims_) >= layer_elems
                and (_MOVE.match(name) or _MOVE.match(op))):
            found.append(f"{name} = [{dims}] {op}")
    return found


def cell_engine(arch: str):
    """An engine (abstract weights, a 64-block pool never allocated) with
    the model widths and engine settings of a benchmark configuration."""
    from repro.core.config import ArchConfig
    from repro.models.lm import LM
    from repro.serving.engine import Engine

    with open(os.path.join(REPO, "bench", "configs", f"{arch}.json")) as f:
        conf = json.load(f)
    es = conf["engine"]
    cfg = ArchConfig(
        name=arch, family="dense", n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qkv_bias=conf.get("attention_bias", conf["model_type"] == "qwen2"),
        tie_embeddings=conf["tie_word_embeddings"])
    return Engine(cfg, LM(cfg).abstract_params(), max_batch=es["max_batch"], n_blocks=64,
                  block_size=es["block_size"],
                  prefill_chunk=es["prefill_chunk"])


def lower_step(eng, kind: str, n_blocks: int, sharding):
    """Compile the engine's decode or chunk step over a pool of
    ``n_blocks`` blocks at the widest table."""
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), eng.params)
    kv = {k: sds((a.shape[0], n_blocks) + tuple(a.shape[2:]), a.dtype)
          for k, a in eng.kv.state.items()}
    i32, per, w = jnp.int32, eng.model.n_periods, CELL_WIDTH
    if kind == "decode":
        b = eng.max_batch
        return eng._fused_step.lower(
            params, kv, {}, sds((b,), i32), sds((b,), i32),
            sds((b, w), i32), sds((b,), jnp.bool_),
            sds((per, b), jnp.bool_)).compile()
    return eng._chunk_step.lower(
        params, kv, {}, sds((1, eng.prefill_chunk), i32), sds((), i32),
        sds((), i32), sds((1, w), i32), sds((), i32),
        sds((per, 1), jnp.bool_)).compile()


@pytest.mark.parametrize("arch,kind,pools", [
    ("qwen1.5-0.5b", "decode", (1920, 3840)),
    ("qwen1.5-0.5b", "chunk", (1920, 3840)),
    ("granite-3-2b", "decode", (1536, 3072)),
])
def test_step_reads_and_writes_the_pool_in_place(one_chip, on_tpu,
                                                 monkeypatch, arch, kind,
                                                 pools):
    """The decode and chunk steps at a benchmark cell's shapes (its batch,
    chunk and widest table) address the pool in place: their temporaries
    do not grow with the pool, and no copy or dynamic slice of a layer's
    pool (or the whole pool) is compiled. A layout the paged read cannot
    take as stored, or a scan that slices the pool per layer, shows here
    as temporaries proportional to ``n_blocks`` and as such instructions.
    """
    import jax
    from repro.serving import cache as C

    real_init = C.init_state
    monkeypatch.setattr(C, "init_state", lambda cfg, dtype=None, sharding=None:
                        jax.eval_shape(lambda: real_init(cfg)))
    eng = cell_engine(arch)
    assert eng._paged_impl == "pallas"
    kv = eng.kv_cfg
    temps = {}
    for n_blocks in pools:
        compiled = lower_step(eng, kind, n_blocks, one_chip)
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        layer_elems = n_blocks * kv.block_size * kv.n_kv_heads * kv.head_dim
        assert pool_moves(text, n_blocks, layer_elems) == [], n_blocks
        temps[n_blocks] = compiled.memory_analysis().temp_size_in_bytes
    assert max(temps.values()) - min(temps.values()) <= 2 ** 20, temps
