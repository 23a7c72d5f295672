"""A model family is one new file. The tests' own hybrid family
(bench/tests/tiny_hybrid.py: Mamba-2 and attention layers, a SwiGLU MLP in
each), put beside bench/families/dense.py under a name that no file of the
harness knows, carries the program's tiny hybrid through the harness:
weights, the pool fit, the lowered steps, a whole run with its per-layer
readers, and the comparison with its reference."""
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import correct, families, flops, model
from bench.tests.small import cell, cpu_only, fixed_pool

NAME = "tiny_hybrid"
SEED = 2**31 + 606
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def program_config():
    """The program's tiny hybrid: attention at every second layer, SSD
    layers between, dense MLPs, no position embedding."""
    from repro.core.config import ArchConfig
    return ArchConfig(name="tiny-hybrid", family="hybrid", n_layers=4,
                      d_model=256, n_heads=8, n_kv_heads=2, head_dim=32,
                      d_ff=512, vocab_size=1024, ssm_state=64,
                      attn_period=2, attn_offset=1, rope_fraction=0.0,
                      tie_embeddings=True).reduced()


def conf_of(cfg):
    """``cfg``'s configuration file, in Granite 4.0-H's keys."""
    return {
        "name": cfg.name, "family": NAME, "model_type": "granitemoehybrid",
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "layer_types": ["attention" if k == "attn" else "mamba"
                        for k in cfg.layer_kinds()],
        "mamba_n_heads": cfg.n_ssm_heads, "mamba_d_head": cfg.ssm_headdim,
        "mamba_d_state": cfg.ssm_state, "mamba_n_groups": cfg.ssm_ngroups,
        "mamba_d_conv": cfg.ssm_conv, "mamba_expand": cfg.ssm_expand,
        "mamba_chunk_size": cfg.ssm_chunk, "position_embedding_type": "nope",
        "attention_bias": False, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps, "tie_word_embeddings": True,
        "engine": {"block_size": 16, "prefill_chunk": 32, "max_batch": 4,
                   "kv_dtype": "bf16", "chips": 1}}


@pytest.fixture
def family(tmp_path, monkeypatch):
    """The family module, as a new file on the families' path."""
    shutil.copy(Path(__file__).with_name(f"{NAME}.py"), tmp_path)
    monkeypatch.setattr(families, "__path__",
                        [*families.__path__, str(tmp_path)])
    sys.modules.pop(f"bench.families.{NAME}", None)
    cpu_only()
    cfg = program_config()
    conf = conf_of(cfg)
    yield SimpleNamespace(mod=families.load(conf), cfg=cfg, conf=conf)
    sys.modules.pop(f"bench.families.{NAME}", None)


def per_slot_state(cfg) -> int:
    """Each SSM layer's SSD state and conv inputs, float32, for one slot."""
    ch = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
    return sum((cfg.n_ssm_heads * cfg.ssm_headdim * cfg.ssm_state
                + (cfg.ssm_conv - 1) * ch) * 4
               for k in cfg.layer_kinds() if k == "ssm")


def test_the_family_builds_the_programs_model(family):
    fam, cfg, conf = family.mod, family.cfg, family.conf
    assert fam.__file__ != str(Path(__file__).with_name(f"{NAME}.py"))
    assert fam.arch_config(conf) == cfg
    assert cfg.layer_kinds() == ("ssm", "attn", "ssm", "attn")
    params = model.make_weights(conf, SEED)
    model.check_layout(cfg, params)
    assert params["blocks"]["pos0"]["mix"]["a_log"].dtype == np.float32


def test_reference_matches_the_program_forward_at_float32(family):
    import jax
    import jax.numpy as jnp
    from bench import reference
    from repro.models.lm import LM

    fam, cfg, conf = family.mod, family.cfg, family.conf
    params = model.make_weights(conf, SEED)
    tokens = np.random.default_rng(SEED).integers(0, cfg.vocab_size,
                                                  reference.ROWS)
    ref = {k: np.asarray(v) for k, v in fam.reference(conf)(
        params, jnp.asarray(tokens, jnp.int32)).items()}
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits = LM(cfg).forward(
            f32, {"tokens": jnp.asarray(tokens, jnp.int32)[None]})
    lg = np.asarray(logits, np.float64)[0, :, :cfg.vocab_size]
    nxt = np.concatenate([tokens[1:], tokens[:1]])
    want = {"max": lg.max(-1), "sd": lg.std(-1),
            "next": np.take_along_axis(lg, nxt[:, None], 1)[:, 0]}
    for k, v in want.items():
        assert ref[k] == pytest.approx(v, rel=1e-3, abs=1e-3), k


def test_counts_read_the_attention_layers_only(family):
    from repro.models.lm import LM

    fam, cfg, conf = family.mod, family.cfg, family.conf
    c = fam.counts(conf, conf["engine"])
    assert c.attn_layers == 2 < cfg.n_layers
    one = c.paged_read(40, 1)
    sec, _, _ = flops.roofline_seconds(c, [(40, 1)], 1e6, 1e6)
    assert sec == max(one) * 2 / 1e6
    # every projection a token multiplies, and the tied head
    names = ("wq", "wk", "wv", "wo", "in_proj", "out_proj", "w_gate",
             "w_up", "w_down")
    import jax
    w = sum(int(np.prod(a.shape)) for p, a in
            jax.tree_util.tree_leaves_with_path(LM(cfg).abstract_params())
            if p[-1].key in names)
    assert c.matmul_params == w + cfg.d_model * cfg.vocab_size
    assert c.state_bytes == per_slot_state(cfg)


def test_the_lowered_steps_take_the_engines_state(family):
    import jax
    cfg, conf = family.cfg, family.conf
    eng = model.build_engine(cfg, model.make_weights(conf, SEED),
                             conf["engine"], 64)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), eng._ssm_states)
    assert model.chip_bytes(eng._ssm_states) == \
        conf["engine"]["max_batch"] * per_slot_state(cfg)
    shard = jax.sharding.SingleDeviceSharding(cpu_only()[0])
    for kind in ("decode", "chunk"):
        got = model._lower(eng, kind, 128, 4, shard).args_info[0][2]
        assert jax.tree.map(lambda a: (a.shape, a.dtype), got) == want
        assert all(a.donated for a in jax.tree.leaves(got))
    model.free_state(eng)
    assert all(a.is_deleted() for a in jax.tree.leaves(
        model.device_state(eng)))


def test_the_pool_fit_counts_the_resident_state(family, monkeypatch):
    cfg, conf = family.cfg, family.conf
    es = conf["engine"]
    monkeypatch.setattr(model, "device_limit", lambda devices: 48 << 20)
    n, info = model.fit_pool(cfg, model.make_weights(conf, SEED), es, 8,
                             cpu_only(), lambda *a: None)
    assert info["state_bytes"] == es["max_batch"] * per_slot_state(cfg) > 0
    need = max(info["decode_plan_bytes"], info["chunk_plan_bytes"]) \
        + info["pool_bytes"] + info["state_bytes"]
    assert n == info["blocks"] > 0 and need <= info["budget_bytes"]


def test_a_run_through_the_harness(family, monkeypatch):
    from bench.run import run_cell

    fixed_pool(monkeypatch)
    bench, wl, _, mix = cell()
    res = run_cell(bench, wl, family.conf, mix, seed=SEED, seconds=2.0,
                   trace=True, devices=cpu_only(), peaks=PEAKS,
                   limits=correct.limits(wl["name"]),
                   t_start=time.monotonic(), out=lambda *a: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    for name in ("compiles_in_window", "batch_occupancy", "step_ms",
                 "kv_blocks_used_share", "mfu"):
        assert name in m, sorted(m)
    assert m["compiles_in_window"]["value"] == 0
    # no Pallas kernel runs on the CPU: the roofline has nothing to read
    assert "paged_attn_roofline" not in m


def test_another_kernel_leaves_the_paged_read_alone(family):
    from bench.layer_metrics import paged_attn_roofline as paged
    from bench.run import Context

    conf = family.conf
    counts = family.mod.counts(conf, conf["engine"])
    step = SimpleNamespace(t0=0.1, t1=0.2, decode_lens=[40, 300],
                           chunk=(64, 32), new_prefill=(64, 32, False))
    run = SimpleNamespace(trace_span=(0.0, 1.0), steps=[step])
    paged_s = {"paged_decode": {"s": 2e-4, "calls": 4},
               "paged_prefix": {"s": 1e-4, "calls": 2}}
    got = []
    for kernels in (paged_s, dict(paged_s, ssd_scan={"s": 5e-3, "calls": 2})):
        ctx = Context(run, conf["engine"], counts, PEAKS, 1,
                      {"kernels": kernels}, 0, None, 64)
        got.append(paged.read(ctx))
    least, _, _ = flops.roofline_seconds(
        counts, [(40, 1), (300, 1), (64, 32)], PEAKS["bf16_flops"],
        PEAKS["hbm_bytes_per_s"])
    assert got == [100.0 * least / (2e-4 + 1e-4)] * 2
