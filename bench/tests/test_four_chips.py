"""A cell on four chips, on four forced CPU devices in a child process
(the parent's JAX has one): the harness builds the program's
tensor-parallel engine on a (data 1, model 4) mesh, draws every weight
straight into the sharding the engine gives it, so no device holds more
than its share, fits the pool with per-device plans, and a short run
through the whole harness is correct against the reference (as is the
program's reading in the control's loop, whose next seed draws into the
same shardings)."""
import json
import os
import subprocess
import sys

from bench.tests.small import BENCH

CHILD = r"""
import json, math, sys, time
import jax
import numpy as np
from bench import correct, model
from bench import run as R
from bench.tests.small import cell

jax.config.update("jax_enable_compilation_cache", False)
bench, wl, conf, mix = cell()
conf.update(hidden_size=128, intermediate_size=256, num_attention_heads=8,
            num_key_value_heads=4, num_hidden_layers=2, vocab_size=512,
            tie_word_embeddings=False)
conf["engine"]["chips"] = 4
model.fit_pool = lambda *a, **k: (64, {"blocks": 64})
devices = jax.devices()[:4]
seed = 2**31 + 404
out = {}

prep = R.prepare(conf, mix, seed, devices, out=lambda *a: None)
eng = prep.eng
out["tp"] = eng.tp_degree
want = model.param_shardings(prep.cfg, eng.mesh)
out["sharded_as_engine"] = all(jax.tree.leaves(jax.tree.map(
    lambda a, s: a.sharding.is_equivalent_to(s, a.ndim), prep.params, want)))
out["engine_holds_the_drawn_arrays"] = all(jax.tree.leaves(jax.tree.map(
    lambda a, b: a is b, prep.params, eng.params)))
# each leaf's share: whole where the resolver replicates it, a quarter
# where it splits one axis over the model axis
share = sum(a.nbytes // (4 if "model" in a.sharding.spec else 1)
            for a in jax.tree.leaves(prep.params))
held = model.bytes_per_chip(prep.params)
out["held"] = sorted(held.values())
out["share"] = share
out["total"] = sum(a.nbytes for a in jax.tree.leaves(prep.params))
out["split"] = sorted(jax.tree_util.keystr(p) for p, a in
                      jax.tree_util.tree_leaves_with_path(prep.params)
                      if "model" in a.sharding.spec)
pool = {k: (a.sharding.shard_shape(a.shape), a.shape)
        for k, a in eng.kv.state.items()}
out["pool"] = {k: [list(s), list(w)] for k, (s, w) in pool.items()}
# the plans are per device: the weights' share and the pool's
compiled = model._lower(eng, "decode", 128, prep.widths[-1],
                        jax.sharding.NamedSharding(
                            eng.mesh, jax.sharding.PartitionSpec()))
pool_share = sum(math.prod(a.sharding.shard_shape(a.shape)) * 2
                 for a in eng.kv.state.values()) * 128 // 64
out["plan_args"] = compiled.memory_analysis().argument_size_in_bytes
out["plan_want"] = share + pool_share

# the control's next seed draws its weights into the same shardings
from bench import control
ctl = control.readings(prep, conf, mix, correct.limits(wl["name"]), seed + 1,
                       2.0)
out["control_seed_sharded"] = all(jax.tree.leaves(jax.tree.map(
    lambda a, s: a.sharding.is_equivalent_to(s, a.ndim), prep.params, want)))
out["control_program_correct"] = ctl["program_correct"]
for a in eng.kv.state.values():
    a.delete()

res = R.run_cell(bench, wl, conf, mix, seed=seed, seconds=2.0, trace=False,
                 devices=devices, peaks={}, limits=correct.limits(wl["name"]),
                 t_start=time.monotonic(), out=lambda *a: None)
out["correct"] = res["correct"]
out["checks"] = res["checks"]
out["count"] = res["device"]["count"]
print(json.dumps(out))
"""


def test_prepare_and_run_on_four_devices():
    repo = BENCH.parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(repo), str(repo / "src")]))
    p = subprocess.run([sys.executable, "-c", CHILD], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["tp"] == 4 and r["count"] == 4
    assert r["sharded_as_engine"] and r["engine_holds_the_drawn_arrays"]
    # no device holds the whole tree, and each holds exactly its share
    assert r["held"] == [r["share"]] * 4 and r["share"] < r["total"]
    # attention heads, the MLP and the untied head split; the embedding
    # and the norms do not
    assert "['head']" in r["split"]
    assert "['blocks']['pos0']['ffn']['w_gate']" in r["split"]
    assert "['blocks']['pos0']['mix']['wk']" in r["split"]
    assert "['embed']" not in r["split"]
    for shard, whole in r["pool"].values():   # 1 of the 4 kv heads
        assert shard[:3] == whole[:3] and shard[3] * 4 == whole[3]
    assert r["plan_args"] >= r["plan_want"]
    assert r["plan_args"] < r["plan_want"] + 4096
    assert r["correct"], r["checks"]
    assert r["control_seed_sharded"] and r["control_program_correct"]
