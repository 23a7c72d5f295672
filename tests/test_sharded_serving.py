"""Model-parallel sharded serving: greedy-token parity against the
single-device engine, shard-layout contracts for the paged pools, and the
one-dispatch-per-step (bounded compile) invariant under a mesh.

These tests need a multi-device jax backend; CI's fast lane forces an
8-device CPU mesh with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(see .github/workflows/ci.yml) and anything above the available device
count skips. The parity contract is exact: a TP-sharded engine must emit
token-identical greedy output — the sharded dense contractions accumulate
in f32 (models/layers.dense) and every activation the sharding constraint
materializes is computed at an explicit precision (layers.swiglu,
blocks._expert_ffn), so TP-vs-single-device differences are f32 reorder
noise, far below greedy decision boundaries, instead of bf16
fusion-dependent rounding.
"""
import functools

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from repro.models.lm import LM
from repro.serving.engine import Engine, Request


def needs_devices(n):
    return pytest.mark.skipif(
        len(jax.devices()) < n,
        reason=f"needs {n} devices (run with XLA_FLAGS="
               f"--xla_force_host_platform_device_count=8)")


@functools.lru_cache(maxsize=None)
def _setup(arch):
    cfg = get_config(arch, reduced=True)
    model = LM(cfg)
    return cfg, model.init(jax.random.PRNGKey(0))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, cfg.vocab_size, n)]
            for n in lens]


def _run(arch, mesh, *, lens=(12, 12, 10, 12), max_new=8, n_blocks=64,
         block_size=8, max_batch=4, **kw):
    cfg, params = _setup(arch)
    eng = Engine(cfg, params, max_batch=max_batch, n_blocks=n_blocks,
                 block_size=block_size, mesh=mesh, **kw)
    for rid, p in enumerate(_prompts(cfg, lens)):
        eng.submit(Request(rid=rid, tokens=list(p), max_new_tokens=max_new))
    eng.run(max_steps=800)
    assert all(r is None for r in eng.running)
    return {r.rid: r.output for r in eng.finished}, eng


# --------------------------------------------------------------------------
# Token parity: the acceptance contract. The full-stack scenario (int8 KV
# + chunked prefill + speculation) runs in the fast lane for qwen at every
# TP degree; the other archs and preemption-under-pressure variants cover
# the remaining axes.
# --------------------------------------------------------------------------


@needs_devices(8)
@pytest.mark.parametrize("tp", [2, 4, 8])
def test_tp_parity_full_stack_qwen(tp):
    """int8 KV + chunked prefill + ngram speculation, TP vs single-device:
    token-identical greedy output and the same verify/chunk schedules."""
    kw = dict(kv_quant="int8", prefill_chunk=4, speculate="ngram",
              spec_depth=4)
    base, beng = _run("qwen1.5-0.5b", None, **kw)
    out, seng = _run("qwen1.5-0.5b", make_local_mesh(model=tp, data=1), **kw)
    assert out == base
    # identical tokens -> identical acceptance history -> identical rounds
    assert seng.stats()["spec_rounds"] == beng.stats()["spec_rounds"]


@needs_devices(2)
@pytest.mark.parametrize("arch", ["mamba2-130m"])
def test_tp_parity_ssm(arch):
    """Pure-SSM arch: the sharded SSM state pools (conv channels / SSD
    heads) carry decode state bit-compatibly with the replicated run."""
    base, _ = _run(arch, None, kv_quant="int8")
    out, _ = _run(arch, make_local_mesh(model=2, data=1), kv_quant="int8")
    assert out == base


@pytest.mark.slow
@needs_devices(8)
@pytest.mark.parametrize("arch,tp", [("mamba2-130m", 8),
                                     ("jamba-v0.1-52b", 2),
                                     ("jamba-v0.1-52b", 4),
                                     ("jamba-v0.1-52b", 8)])
def test_tp_parity_hybrid_slow(arch, tp):
    """jamba hybrid (attn + ssm + moe; EP all-to-all at tp | n_experts,
    mlp-axis-sharded local dispatch otherwise) and the 8-way ssm stack,
    with int8 KV and chunked prefill."""
    kw = dict(kv_quant="int8", prefill_chunk=4)
    base, _ = _run(arch, None, **kw)
    out, _ = _run(arch, make_local_mesh(model=tp, data=1), **kw)
    assert out == base


@needs_devices(2)
def test_tp_parity_under_preemption():
    """An undersized pool forces evictions; the sharded engine must make
    the same scheduling decisions (host-global policy) and emit the same
    tokens, and scrubbed/released pages must not leak on either side."""
    kw = dict(n_blocks=6, block_size=4, max_batch=3, lens=(8, 8, 8, 8),
              max_new=6, prefill_chunk=4)
    base, beng = _run("qwen1.5-0.5b", None, **kw)
    out, seng = _run("qwen1.5-0.5b", make_local_mesh(model=2, data=1), **kw)
    assert out == base
    assert seng.sched.n_preemptions == beng.sched.n_preemptions > 0
    assert seng.alloc.n_free == seng.alloc.n_blocks


# --------------------------------------------------------------------------
# Structural contracts
# --------------------------------------------------------------------------


@needs_devices(4)
def test_kv_pool_sharded_on_kv_heads():
    """The paged pool splits its folded KV-head axis over the model axis
    (when the degree divides the head count), each shard holding whole
    heads; the int8 scales ride along. Layout (L, nb, bs, K*hd)."""
    from jax.sharding import PartitionSpec as P
    cfg, params = _setup("qwen1.5-0.5b")
    mesh = make_local_mesh(model=4, data=1)
    eng = Engine(cfg, params, max_batch=2, n_blocks=16, block_size=8,
                 kv_quant="int8", mesh=mesh)
    assert cfg.n_kv_heads % 4 == 0  # smoke config shards 4 kv heads 4-ways
    for key in ("k", "v", "k_scale", "v_scale"):
        spec = eng.kv.state[key].sharding.spec
        assert tuple(spec) == (None, None, None, "model"), (key, spec)
    # each device holds K/4 whole heads of every page
    hd = cfg.head_dim
    for shard in eng.kv.state["k"].addressable_shards:
        assert shard.data.shape[3] == cfg.n_kv_heads // 4 * hd


@needs_devices(2)
def test_tp_one_dispatch_per_step_contract():
    """trace_counts under a mesh must match the unsharded engine exactly:
    sharding lives inside the jitted steps (GSPMD partitions one
    executable), so TP never adds a step kind, a retrace, or a dispatch."""
    kw = dict(kv_quant="int8", prefill_chunk=4, speculate="ngram",
              spec_depth=4)
    _, beng = _run("qwen1.5-0.5b", None, **kw)
    _, seng = _run("qwen1.5-0.5b", make_local_mesh(model=2, data=1), **kw)
    assert dict(seng.trace_counts) == dict(beng.trace_counts)
    # bounded compile: at most one trace per (kind, T, table-bucket) key
    assert all(v == 1 for v in seng.trace_counts.values())


@needs_devices(2)
def test_mesh_requires_fused_mode():
    cfg, params = _setup("qwen1.5-0.5b")
    with pytest.raises(ValueError, match="model-parallel"):
        Engine(cfg, params, mode="legacy",
               mesh=make_local_mesh(model=2, data=1))


@needs_devices(2)
def test_tp_indivisible_heads_degrade_to_replication():
    """jamba smoke has 2 kv heads: at tp=8... — here tp=2 divides, so use
    an arch/TP pair that does NOT divide (qwen smoke has 4 kv heads; force
    a 3-wide model axis only if available, otherwise replicate check at
    tp=8 is covered by the slow lane). This test pins the *degrade, don't
    crash* contract on the pool spec resolution itself."""
    import dataclasses
    from repro.parallel.sharding import make_serving_ctx
    cfg, _ = _setup("jamba-v0.1-52b")
    mesh = make_local_mesh(model=2, data=1)
    ctx = make_serving_ctx(cfg, mesh)
    # folded kv head axis of the pool: sharded iff the head count divides
    k = max(cfg.n_kv_heads, 1)
    spec = ctx.spec_for("kv_pool", (2, 8, 8, k * 16))
    expected = "model" if k % 2 == 0 else None
    assert spec[3] == expected
    # a head count the degree does not divide replicates instead of
    # raising, even where the folded axis (3 heads of 16) would divide
    odd = make_serving_ctx(dataclasses.replace(cfg, n_kv_heads=3), mesh)
    assert odd.spec_for("kv_pool", (2, 8, 8, 3 * 16))[3] is None
    assert odd.spec_for("kv_pool", (2, 8, 8, 3))[3] is None


def test_serve_cli_model_parallel_matches_tp1_in_subprocess():
    """``serve --reduced --model-parallel 4`` on four forced CPU devices
    emits the TP=1 tokens. Runs in a child so the parent needs no forced
    devices (the tier-1 command forces none, which skips the tests above)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import json\n"
        "from repro.launch import serve\n"
        "out = {}\n"
        "for tp in (1, 4):\n"
        "    eng = serve.main(['--reduced', '--model-parallel', str(tp)])\n"
        "    assert eng.tp_degree == tp\n"
        "    out[tp] = {r.rid: r.output for r in eng.finished}\n"
        "print(json.dumps(out))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert len(out["1"]) == 8
    assert out["4"] == out["1"]


def _bench_common():
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import common
    return common


def test_bench_tp_rows_run_in_process_off_cpu(monkeypatch):
    """On a chip host the TP rows run in this process, which holds the
    chips; degrees wider than the device count are left out."""
    common = _bench_common()
    monkeypatch.setattr(common.jax, "default_backend", lambda: "tpu")
    rows = list(common.run_model_parallel_rows(
        "unused", lambda tp: {"tp": tp}, (1, 2, 4), 8))
    assert rows == [(tp, {"tp": tp}) for tp in (1, 2, 4)
                    if tp <= jax.device_count()]


def test_bench_tp_rows_failed_child_raises():
    """A TP degree whose child process fails stops the sweep."""
    common = _bench_common()
    with pytest.raises(RuntimeError, match="tp2 child failed"):
        list(common.run_model_parallel_rows(
            "benchmarks.no_such_module", None, (2,), 2))
