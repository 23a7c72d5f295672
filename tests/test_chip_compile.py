"""Compile the serving path for a described TPU v5e, with no chip attached.

The TPU compiler is installed even where no chip is, and it refuses what
the chip would refuse: a Pallas block that breaks Mosaic's tiling rule, a
program that does not fit the chip's memory. These tests compile the paged
attention kernel at qwen1.5-0.5b's published widths for every window
``chip_smoke.py`` runs, and the fused decode step at the smoke's pool.
Nothing runs, so they say nothing about results or speed.

The topology is described inside a module fixture (only one process at a
time may load the TPU library, and pytest workers import every test file).
"""
import functools
import os
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

    page = (n_blocks, bs, cfg.n_kv_heads, cfg.head_dim)
    scale = sds(page[:3] + (1,), jnp.float32) if kv == "int8" else None

    def read(q, k, v, table, lengths, ks, vs):
        return fd.paged_flash_prefix_partial(
            q, k, v, table, lengths, k_scale=ks, v_scale=vs,
            impl="pallas", interpret=False)

    compiled = jax.jit(read).lower(
        sds((b, t, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
        sds(page, store), sds(page, store), sds((b, mb), jnp.int32),
        sds((b,), jnp.int32), scale, scale).compile()
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

    page = sds((n_blocks, bs, kv, d), jnp.bfloat16)
    table, lengths = sds((b, mb), jnp.int32), sds((b,), jnp.int32)
    if kernel == "paged_decode":
        read, q = fd.paged_flash_decode_partial, sds((b, h, d), jnp.bfloat16)
    else:
        read = fd.paged_flash_prefix_partial
        q = sds((b, t, h, d), jnp.bfloat16)
    fn = functools.partial(read, impl="pallas", interpret=False)
    text = jax.jit(fn).lower(q, page, page, table, lengths).as_text()
    assert f'kernel_name = "{kernel}"' in text
    assert "@tpu_custom_call" in text
