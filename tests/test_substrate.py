"""Substrate tests: quantization, checkpoint/restart/elastic restore, data
pipeline determinism, pipeline parallelism, gradient compression, HLO
analyzer, sharding resolver."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import Technique
from repro.launch.mesh import make_mesh


# --------------------------------------------------------------------------
# quantization
# --------------------------------------------------------------------------

def test_nf4_roundtrip_error_bounded():
    from repro.quant.qtensor import quantize_nf4
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 256), jnp.float32) * 0.1
    qt = quantize_nf4(w)
    wd = qt.dequantize(jnp.float32)
    rel = float(jnp.linalg.norm(wd - w) / jnp.linalg.norm(w))
    assert rel < 0.12, rel             # NF4 typical ~8% relative error
    assert qt.nbytes() < 0.6 * w.size * 2   # < 0.6x of bf16 storage


def test_int8_roundtrip_error_bounded():
    from repro.quant.qtensor import quantize_int8
    w = jax.random.normal(jax.random.PRNGKey(0), (128, 512), jnp.float32)
    qt = quantize_int8(w)
    wd = qt.dequantize(jnp.float32)
    rel = float(jnp.linalg.norm(wd - w) / jnp.linalg.norm(w))
    assert rel < 0.01, rel


def test_opt8_blockwise_moments():
    from repro.train.optimizer import _o8_encode, _o8_decode
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,), jnp.float32)
    rec = _o8_decode(_o8_encode(x))
    assert float(jnp.max(jnp.abs(rec - x))) < 0.05


# --------------------------------------------------------------------------
# checkpointing / fault tolerance / elasticity
# --------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_retention(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    state = {"w": jnp.arange(12.0).reshape(3, 4),
             "opt": {"m": jnp.ones((5,)), "step": jnp.int32(7)}}
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (10, 20, 30):
        mgr.save(s, state)
    assert mgr.all_steps() == [20, 30]       # retention
    restored, step = mgr.restore(state)
    assert step == 30
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))


def test_checkpoint_ignores_uncommitted(tmp_path):
    from repro.checkpoint.manager import CheckpointManager, COMMIT_MARKER
    mgr = CheckpointManager(str(tmp_path))
    state = {"w": jnp.ones((2,))}
    mgr.save(5, state)
    # simulate a preempted save: committed dir without marker
    broken = tmp_path / "step_000000009"
    broken.mkdir()
    (broken / "manifest.json").write_text("{}")
    assert mgr.latest_step() == 5            # partial write ignored


def test_checkpoint_async_save(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    state = {"w": jnp.ones((256, 256))}
    mgr.save(1, state, blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 1


def test_trainer_checkpoint_restart_resumes_stream(tmp_path):
    """Kill-and-resume: final state after restart == uninterrupted run."""
    from repro.core.config import ShapeSpec
    from repro.core.trainer import Trainer, TrainerConfig
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    shape = ShapeSpec("tiny", 32, 4, "train")
    tech = Technique()

    def run(steps, resume, d):
        t = Trainer(cfg, shape, tech,
                    TrainerConfig(steps=steps, checkpoint_every=2,
                                  checkpoint_dir=str(d), resume=resume,
                                  log_every=1, async_checkpoint=False))
        out = t.run()
        return t.state, out

    s_full, _ = run(4, "none", tmp_path / "a")
    _ = run(2, "none", tmp_path / "b")
    s_resumed, out = run(4, "auto", tmp_path / "b")
    assert out["final_step"] == 4
    a = jax.tree_util.tree_leaves(s_full["params"])[1]
    b = jax.tree_util.tree_leaves(s_resumed["params"])[1]
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=1e-6)


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------

def test_data_deterministic_and_host_sharded():
    from repro.data.pipeline import DataConfig, SyntheticLM
    base = dict(vocab_size=1000, seq_len=64, global_batch=8)
    a = SyntheticLM(DataConfig(**base, seed=1)).batch_at(3)
    b = SyntheticLM(DataConfig(**base, seed=1)).batch_at(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # different hosts draw different data, each 1/N of the batch
    h0 = SyntheticLM(DataConfig(**base, seed=1, host_id=0, n_hosts=2))
    h1 = SyntheticLM(DataConfig(**base, seed=1, host_id=1, n_hosts=2))
    b0, b1 = h0.batch_at(0), h1.batch_at(0)
    assert b0["tokens"].shape[0] == 4
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_prefetcher_overlaps():
    from repro.data.pipeline import DataConfig, Prefetcher, SyntheticLM
    ds = SyntheticLM(DataConfig(vocab_size=100, seq_len=16, global_batch=2))
    pf = Prefetcher(iter(ds))
    b1 = next(pf)
    b2 = next(pf)
    assert b1["tokens"].shape == (2, 16)
    assert not np.array_equal(b1["tokens"], b2["tokens"])
    pf.stop()


# --------------------------------------------------------------------------
# pipeline parallelism (multi host-device)
# --------------------------------------------------------------------------

def test_pipeline_forward_matches_sequential():
    n_dev = len(jax.devices())
    if n_dev < 2:
        pytest.skip("needs >=2 devices (run under dryrun env for more)")
    stages = 2
    mesh = make_mesh((stages,), ("pipe",))
    from repro.parallel.pipeline import pipeline_forward, split_stages
    d = 16
    w = jax.random.normal(jax.random.PRNGKey(0), (4, d, d), jnp.float32) * 0.3

    def stage_fn(p, x):     # p: (L/S, d, d)
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        y, _ = jax.lax.scan(body, x, p)
        return y

    x = jax.random.normal(jax.random.PRNGKey(1), (6, 8, d), jnp.float32)
    # sequential reference
    ref = []
    for m in range(6):
        y = x[m]
        for l in range(4):
            y = jnp.tanh(y @ w[l])
        ref.append(y)
    ref = jnp.stack(ref)
    fn = pipeline_forward(mesh, "pipe", stage_fn, n_micro=6)
    with mesh:
        out = jax.jit(fn)(split_stages(w, stages), x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_bubble_fraction():
    from repro.parallel.pipeline import bubble_fraction
    assert bubble_fraction(1, 4) == pytest.approx(3 / 4)
    assert bubble_fraction(32, 4) == pytest.approx(3 / 35)


# --------------------------------------------------------------------------
# gradient compression
# --------------------------------------------------------------------------

def test_grad_compression_error_feedback_converges():
    from repro.parallel.compression import compress_grad, decompress_grad
    g = jax.random.normal(jax.random.PRNGKey(0), (512,), jnp.float32)
    err = jnp.zeros_like(g)
    # accumulated reconstruction over steps tracks accumulated gradient
    total_recon = jnp.zeros_like(g)
    for i in range(8):
        q, s, err = compress_grad(g, err)
        total_recon += decompress_grad(q, s, g.shape)
    rel = float(jnp.linalg.norm(total_recon - 8 * g) / jnp.linalg.norm(8 * g))
    assert rel < 0.01, rel   # error feedback: bias does not accumulate


# --------------------------------------------------------------------------
# HLO analyzer
# --------------------------------------------------------------------------

def test_hlo_analyzer_counts_scan_trips():
    from repro.core.hloanalysis import analyze_hlo

    def f(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=6)
        return y

    spec = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(f).lower(spec, spec).compile()
    st = analyze_hlo(c.as_text())
    expect = 6 * 2 * 128 ** 3
    assert abs(st.dot_flops - expect) / expect < 1e-6
    assert 6 in st.while_trip_counts.values()


def test_hlo_analyzer_nested_scans_multiply():
    from repro.core.hloanalysis import analyze_hlo

    def f(w, x):
        def outer(c, _):
            def inner(ci, _):
                return jnp.tanh(ci @ w), None
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y

    spec = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = jax.jit(f).lower(spec, spec).compile()
    st = analyze_hlo(c.as_text())
    expect = 12 * 2 * 64 ** 3
    assert abs(st.dot_flops - expect) / expect < 1e-6


# --------------------------------------------------------------------------
# sharding resolver (pure logic, no devices needed)
# --------------------------------------------------------------------------

def test_sharding_resolver_zero_stages():
    import jax as _jax
    from repro.parallel.sharding import make_shard_ctx, resolve_spec
    cfg = get_config("granite-3-2b")
    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    ctx = make_shard_ctx(cfg, Technique(zero_stage=3), FakeMesh())
    # attention q: heads sharded by TP, embed by ZeRO
    spec = resolve_spec(ctx, "wq", (40, 2048, 32, 64),
                        ("layers", "embed", "q_heads", "head_dim"), zero=True)
    assert spec == jax.sharding.PartitionSpec(None, "data", "model", None)
    # kv heads (8 < 16): replicated on the head axis, ZeRO on embed
    spec = resolve_spec(ctx, "wk", (40, 2048, 8, 64),
                        ("layers", "embed", "kv_heads", "head_dim"),
                        zero=True)
    assert spec == jax.sharding.PartitionSpec(None, "data", None, None)
    # no zero: replicated except TP
    spec = resolve_spec(ctx, "w_up", (40, 2048, 8192),
                        ("layers", "embed", "mlp"), zero=False)
    assert spec == jax.sharding.PartitionSpec(None, None, "model")
