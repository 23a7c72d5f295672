"""Speculative decoding subsystem: proposer units, exact-rollback KV/SSM
state under partial acceptance, spec-on == spec-off greedy parity (both
proposers), preemption safety, bounded retracing, adaptive depth back-off,
and the new Engine.stats() speculation fields."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.lm import LM
from repro.serving.cache import PagedKVCache, PagedKVConfig
from repro.serving.engine import Engine, Request
from repro.serving.speculate import (DraftModelProposer, NGramProposer,
                                     Speculator, build_speculator)


def _params(cfg):
    return LM(cfg).init(jax.random.PRNGKey(0))


def _repetitive_prompts(cfg, lens, seed=0):
    from repro.data.pipeline import repetitive_requests
    return [repetitive_requests(1, cfg.vocab_size, prompt_len=t,
                                pattern_len=6, seed=seed)[0] for t in lens]


class ScriptedProposer:
    """Proposes the reference continuation for ``good`` tokens, then a
    garbage tail — forces a deterministic partial-acceptance pattern."""

    def __init__(self, ref, good, garbage=7):
        self.ref, self.good, self.garbage = ref, good, garbage

    def propose(self, req, k):
        i = len(req.output)
        ref = self.ref[req.rid] if isinstance(self.ref, dict) else self.ref
        if i >= len(ref):
            return []
        props = ref[i: i + min(k, self.good)]
        if len(props) < k:
            props = props + [self.garbage] * (k - len(props))
        return props[:k]


# ---------------------------------------------------------------------------
# Proposer units
# ---------------------------------------------------------------------------


def _req(tokens, output):
    return types.SimpleNamespace(tokens=list(tokens), output=list(output))


def test_ngram_proposer_lookup():
    p = NGramProposer(max_ngram=3)
    # tail [11, 12] continues [13, 20] at its earlier occurrence
    assert p.propose(_req([10, 11, 12, 13, 20, 30, 11], [12]), 2) == [13, 20]
    # most recent match wins: 1,2 -> 9 (not 5)
    assert p.propose(_req([1, 2, 5, 1, 2, 9], [1, 2]), 1) == [9]
    # proposal truncated at the context end
    assert p.propose(_req([4, 4, 4], [4]), 8) == [4]
    # no repeated n-gram: silent
    assert p.propose(_req([1, 2, 3, 4, 5], []), 4) == []


def test_ngram_prefers_longer_match():
    p = NGramProposer(max_ngram=3)
    # the 1-gram [2] recurs at index 1 (-> 7) but the 3-gram [9, 1, 2]
    # anchors the later occurrence (-> 8): longest n wins
    ctx = [9, 1, 2, 8, 0, 9, 1, 2]
    assert p.propose(_req(ctx, []), 1) == [8]


# ---------------------------------------------------------------------------
# Kernel-level contract: paged prefix partial + fresh-window causal partial,
# LSE-merged, equals dense attention over [prefix; window]
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quant", [False, True])
def test_prefix_and_self_partials_merge_to_full_attention(quant):
    from repro.kernels import flash_decode as fd
    from repro.serving import cache as C

    rng = jax.random.PRNGKey(0)
    b, t, h, n_kv, d, bs, nb = 2, 3, 4, 2, 16, 4, 6
    lengths = jnp.asarray([9, 5], jnp.int32)
    table = jnp.asarray([[5, 0, 2, 0], [3, 1, 0, 0]], jnp.int32)
    keys = jax.random.split(rng, 4)
    k_pages = jax.random.normal(keys[0], (nb, bs, n_kv, d), jnp.float32)
    v_pages = jax.random.normal(keys[1], (nb, bs, n_kv, d), jnp.float32)
    q = jax.random.normal(keys[2], (b, t, h, d), jnp.float32)
    kf = jax.random.normal(keys[3], (b, t, n_kv, d), jnp.float32)
    vf = jax.random.normal(jax.random.fold_in(rng, 9),
                           (b, t, n_kv, d), jnp.float32)
    ks = vs = None
    if quant:
        k_pages, ks = C.quant_encode(k_pages, "int8")
        v_pages, vs = C.quant_encode(v_pages, "int8")
    # the cache's storage: one layer of a (L, nb, bs, K*hd) pool
    o_c, m_c, l_c = fd.paged_flash_prefix_partial(
        q, k_pages.reshape(1, nb, bs, n_kv * d),
        v_pages.reshape(1, nb, bs, n_kv * d), 0, table, lengths,
        k_scale=None if ks is None else ks.reshape(1, nb, bs, n_kv),
        v_scale=None if vs is None else vs.reshape(1, nb, bs, n_kv))
    o_n, m_n, l_n = fd.causal_self_partial(q, kf, vf)
    got = fd.merge_partials([(o_c, m_c, l_c), (o_n, m_n, l_n)])
    # dense oracle: gather pages, concat the fresh window at each row's
    # true positions, causal mask relative to the prefix length
    kd = C.quant_decode(k_pages, ks, jnp.float32)[table].reshape(
        b, -1, n_kv, d)
    vd = C.quant_decode(v_pages, vs, jnp.float32)[table].reshape(
        b, -1, n_kv, d)
    s_cache = bs * table.shape[1]
    scale = 1.0 / np.sqrt(d)
    for bi in range(b):
        ln = int(lengths[bi])
        k_full = jnp.concatenate([kd[bi, :ln], kf[bi]], axis=0)
        v_full = jnp.concatenate([vd[bi, :ln], vf[bi]], axis=0)
        qg = q[bi].reshape(t, n_kv, h // n_kv, d)
        s = jnp.einsum("ikgd,jkd->ikgj", qg, k_full) * scale
        qpos = ln + jnp.arange(t)[:, None, None, None]
        jpos = jnp.arange(ln + t)[None, None, None, :]
        s = jnp.where(qpos >= jpos, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        want = jnp.einsum("ikgj,jkd->ikgd", p, v_full).reshape(t, h, d)
        np.testing.assert_allclose(np.asarray(got[bi]), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# cache.truncate_slots: the host-side rollback/scrub primitive
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_truncate_slots_rewinds_to_prefix(kv_quant):
    cfg = PagedKVConfig(n_layers=2, n_kv_heads=2, head_dim=16, n_blocks=8,
                        block_size=4, kv_quant=kv_quant)
    kv = PagedKVCache(cfg)
    pristine = {k: np.asarray(v, np.float32) for k, v in kv.state.items()}
    k = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 2, 16),
                          jnp.bfloat16)
    blocks = [6, 1, 3]
    kv.write_prefill((k, k), blocks)
    written = {kk: np.asarray(v, np.float32) for kk, v in kv.state.items()}
    kv.truncate_slots(blocks, keep_tokens=5)
    for kk in kv.state:
        got = np.asarray(kv.state[kk], np.float32)
        # kept prefix: positions 0..4 (block 6 whole, block 1 offset 0)
        np.testing.assert_array_equal(got[:, 6], written[kk][:, 6])
        np.testing.assert_array_equal(got[:, 1, 0], written[kk][:, 1, 0])
        # rewound tail: bitwise back to the never-written state
        np.testing.assert_array_equal(got[:, 1, 1:], pristine[kk][:, 1, 1:])
        np.testing.assert_array_equal(got[:, 3], pristine[kk][:, 3])
    # full scrub (keep_tokens=0) restores everything
    kv.truncate_slots(blocks, keep_tokens=0)
    for kk in kv.state:
        np.testing.assert_array_equal(np.asarray(kv.state[kk], np.float32),
                                      pristine[kk])


# ---------------------------------------------------------------------------
# Greedy parity: spec-on emits token-identical output to spec-off
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,lens", [
    ("qwen1.5-0.5b", (12, 9, 14, 20)),
    ("mamba2-130m", (24, 18, 27)),
])
def test_spec_ngram_greedy_parity(arch, lens):
    cfg = get_config(arch, reduced=True)
    params = _params(cfg)
    prompts = _repetitive_prompts(cfg, lens)
    outs, rounds = {}, 0
    for spec in (None, "ngram"):
        eng = Engine(cfg, params, max_batch=3, n_blocks=64, block_size=8,
                     speculate=spec, spec_depth=4)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, tokens=list(p), max_new_tokens=10))
        done = eng.run(max_steps=400)
        assert len(done) == len(prompts)
        assert eng.alloc.n_free == eng.alloc.n_blocks
        outs[spec] = {r.rid: r.output for r in done}
        if spec:
            rounds = eng.stats()["spec_rounds"]
    assert outs[None] == outs["ngram"]
    assert rounds > 0          # the verify path actually ran


def test_spec_ngram_parity_int8_kv():
    """Speculation composes with the int8-quantized cache: the verify
    window attends to its fresh tokens as they will be stored (quant
    roundtrip), so spec-on tokens still match spec-off exactly."""
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = _params(cfg)
    prompts = _repetitive_prompts(cfg, (12, 18))
    outs = {}
    for spec in (None, "ngram"):
        eng = Engine(cfg, params, max_batch=2, n_blocks=64, block_size=8,
                     kv_quant="int8", speculate=spec, spec_depth=4)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, tokens=list(p), max_new_tokens=10))
        done = eng.run(max_steps=300)
        assert len(done) == 2
        outs[spec] = {r.rid: r.output for r in done}
        if spec:
            assert eng.stats()["spec_rounds"] > 0
    assert outs[None] == outs["ngram"]


@pytest.mark.parametrize("arch,chunk,lens", [
    ("qwen1.5-0.5b", 8, (8, 64)),
    ("mamba2-130m", 32, (40, 96)),
])
def test_spec_with_chunked_prefill_parity(arch, chunk, lens):
    """A request mid-chunked-prefill holds an INACTIVE verify row while
    the running batch speculates: its carried (conv, ssd) state and pages
    must not be advanced by the verify windows (the speculation analogue
    of the fused step's active-slot mask). Greedy tokens must match the
    same chunked engine without speculation. The scripted proposer forces
    partial-acceptance verify rounds to actually fire while the long
    prompt is still paging out."""
    cfg = get_config(arch, reduced=True)
    params = _params(cfg)
    prompts = _repetitive_prompts(cfg, lens)

    def run(spec):
        eng = Engine(cfg, params, max_batch=2, n_blocks=64, block_size=8,
                     prefill_chunk=chunk, speculate=spec, spec_depth=4)
        eng.submit(Request(rid=0, tokens=list(prompts[0]),
                           max_new_tokens=16))
        eng.step()                  # rid 0 starts decoding first
        eng.submit(Request(rid=1, tokens=list(prompts[1]),
                           max_new_tokens=6))
        done = eng.run(max_steps=400)
        assert len(done) == 2
        assert eng.alloc.n_free == eng.alloc.n_blocks
        return eng, {r.rid: r.output for r in done}

    _, ref = run(None)
    eng, out = run(ScriptedProposer(ref, good=2))
    assert eng.stats()["spec_rounds"] > 0
    assert out == ref


@pytest.mark.slow
def test_spec_hybrid_arch_greedy_parity():
    """Hybrid attn+ssm+moe stack (jamba) through the unified paged read:
    spec-on output stays token-identical to spec-off. The n-gram proposer
    is silent on this arch's non-periodic greedy stream, so a scripted
    proposer forces partial-acceptance verify rounds to actually fire."""
    cfg = get_config("jamba-v0.1-52b", reduced=True)
    params = _params(cfg)
    prompts = _repetitive_prompts(cfg, (18, 25))

    def run(spec):
        eng = Engine(cfg, params, max_batch=3, n_blocks=64, block_size=8,
                     speculate=spec, spec_depth=4)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, tokens=list(p), max_new_tokens=10))
        done = eng.run(max_steps=400)
        assert len(done) == len(prompts)
        assert eng.alloc.n_free == eng.alloc.n_blocks
        return eng, {r.rid: r.output for r in done}

    _, ref = run(None)
    eng, out = run(ScriptedProposer(ref, good=2))
    assert eng.stats()["spec_rounds"] > 0      # verify rounds really ran
    assert out == ref


@pytest.mark.slow
def test_spec_draft_greedy_parity():
    """A draft model with *different* (random) weights proposes mostly
    wrong tokens; acceptance filtering must still leave the target's
    greedy stream untouched."""
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = _params(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=t).tolist()
               for t in (10, 15)]
    outs = {}
    for spec in (None, DraftModelProposer(cfg, seed=1)):
        eng = Engine(cfg, params, max_batch=2, n_blocks=64, block_size=8,
                     speculate=spec, spec_depth=3)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, tokens=list(p), max_new_tokens=8))
        done = eng.run(max_steps=200)
        assert len(done) == 2
        outs[bool(spec)] = {r.rid: r.output for r in done}
        if spec:
            assert eng.stats()["spec_rounds"] > 0
    assert outs[False] == outs[True]


def test_spec_self_draft_accepts_everything():
    """Drafting with the target's own params is the acceptance upper
    bound: every proposal matches the verify argmax, so max_new tokens
    arrive in ~max_new/(depth+1) verify rounds."""
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = _params(cfg)
    prompt = list(range(1, 11))
    eng = Engine(cfg, params, max_batch=1, n_blocks=32, block_size=8,
                 speculate=DraftModelProposer(cfg, params), spec_depth=4)
    eng.submit(Request(rid=0, tokens=prompt, max_new_tokens=11))
    done = eng.run(max_steps=50)
    st = eng.stats()
    assert len(done[0].output) == 11
    assert st["accept_rate"] == 1.0
    assert st["spec_rounds"] <= 3      # ~5 tokens per round, not 1


# ---------------------------------------------------------------------------
# Exact rollback: partial acceptance leaves KV/SSM state bitwise-identical
# to a run that never speculated
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mamba2-130m"])
def test_spec_partial_acceptance_bitwise_rollback(arch):
    cfg = get_config(arch, reduced=True)
    params = _params(cfg)
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, cfg.vocab_size, size=13).tolist()

    def run(spec):
        eng = Engine(cfg, params, max_batch=2, n_blocks=32, block_size=8,
                     speculate=spec, spec_depth=4)
        eng.submit(Request(rid=0, tokens=list(prompt), max_new_tokens=10))
        done = eng.run(max_steps=200)
        return eng, done[0].output

    eng_off, ref = run(None)
    # 2 correct tokens then garbage per round -> every verify round is a
    # partial acceptance with a rejected tail
    eng_on, out = run(ScriptedProposer(ref, good=2))
    st = eng_on.stats()
    assert out == ref
    assert 0.0 < st["accept_rate"] < 1.0
    # KV lengths: same blocks held at finish (none), same pool state
    assert eng_on.alloc.n_free == eng_on.alloc.n_blocks
    # rejected appends routed to the null-write sentinel: the FULL paged
    # storage is bitwise-identical to the non-speculative replay
    for kk in eng_off.kv.state:
        np.testing.assert_array_equal(
            np.asarray(eng_off.kv.state[kk], np.float32),
            np.asarray(eng_on.kv.state[kk], np.float32))
    # SSM state rolled back by snapshot selection, never recomputed
    for a, b in zip(jax.tree_util.tree_leaves(eng_off._ssm_states),
                    jax.tree_util.tree_leaves(eng_on._ssm_states)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# ---------------------------------------------------------------------------
# Preemption of a speculating request
# ---------------------------------------------------------------------------


def test_spec_preemption_no_leak_token_exact():
    """An undersized pool evicts speculating requests mid-flight: every
    request still completes with the uncontended run's exact tokens, and
    no KV blocks leak."""
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = _params(cfg)
    prompts = _repetitive_prompts(cfg, (8, 8, 8, 8), seed=1)

    def run(n_blocks, spec):
        eng = Engine(cfg, params, max_batch=3, n_blocks=n_blocks,
                     block_size=4, speculate=spec, spec_depth=4)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, tokens=list(p), max_new_tokens=6))
        done = eng.run(max_steps=500)
        return eng, {r.rid: r.output for r in done}

    _, ref = run(64, None)                   # uncontended, no speculation
    eng, out = run(6, "ngram")               # pressure + speculation
    assert out == ref
    assert eng.sched.n_preemptions > 0
    assert eng.alloc.n_free == eng.alloc.n_blocks
    assert all(r is None for r in eng.running)


# ---------------------------------------------------------------------------
# Bounded compile, stats, policy
# ---------------------------------------------------------------------------


def test_spec_bounded_compile_and_stats():
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = _params(cfg)
    eng = Engine(cfg, params, max_batch=2, n_blocks=64, block_size=4,
                 speculate="ngram", spec_depth=4)
    eng.warmup(16)
    for rid in range(4):
        eng.submit(Request(rid=rid, tokens=_repetitive_prompts(
            cfg, (8,), seed=rid)[0], max_new_tokens=8))
    eng.run(max_steps=200)
    verify_keys = {k: v for k, v in eng.trace_counts.items()
                   if k[0] == "verify"}
    assert verify_keys                        # the verify path compiled
    # one executable per (window-bucket, table-bucket): never retraced
    assert all(v == 1 for v in verify_keys.values())
    assert all(t in (1, 2, 4, 5) for _, t, _ in verify_keys)
    st = eng.stats()
    for k in ("spec_rounds", "spec_proposed_tokens", "spec_accepted_tokens",
              "accept_rate", "spec_depth_hist"):
        assert k in st
    assert st["spec_proposed_tokens"] >= st["spec_accepted_tokens"]
    assert sum(st["spec_depth_hist"].values()) == st["spec_rounds"]
    # reset_stats clears the speculation counters too
    eng.reset_stats()
    assert eng.stats()["spec_rounds"] == 0


def test_tpot_counts_all_spec_accepted_tokens():
    """tpot() divides by every emitted token, not by engine steps: with a
    fully-accepting proposer the same generation takes ~1/(depth+1) the
    steps, and under a tick-per-call fake clock the per-token time must
    shrink accordingly. A step-counting tpot would stay equal."""
    import itertools

    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = _params(cfg)
    prompt = list(range(1, 9))

    def run(spec):
        tick = itertools.count()
        eng = Engine(cfg, params, max_batch=1, n_blocks=32, block_size=8,
                     speculate=spec, spec_depth=4,
                     clock=lambda: float(next(tick)))
        eng.submit(Request(rid=0, tokens=list(prompt), max_new_tokens=9))
        done = eng.run(max_steps=100)
        return eng, done[0]

    eng_off, r_off = run(None)
    eng_on, r_on = run(ScriptedProposer(list(r_off.output), good=8))
    assert r_on.output == r_off.output
    assert eng_on.steps < eng_off.steps       # several tokens per step
    # same token count over fewer clock ticks -> strictly smaller tpot
    assert r_on.tpot() < r_off.tpot()
    # the denominator is every emitted token after the prefill token
    assert r_on.tpot() == ((r_on.finish_time - r_on.first_token_time)
                           / (len(r_on.output) - 1))


def test_stats_roundtrip_after_reset():
    """warmup -> warm burst -> reset_stats -> measured window: stats()
    reflects ONLY the measured window (request count, token counters,
    spec proposed/accepted counters and the depth histogram all restart),
    and the percentile fields stay finite on the empty and singleton
    windows either side of the reset."""
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = _params(cfg)
    eng = Engine(cfg, params, max_batch=2, n_blocks=64, block_size=8,
                 speculate="ngram", spec_depth=4)
    eng.warmup(32)
    st0 = eng.stats()                     # empty window: zeros, no raise
    assert st0["requests"] == 0 and st0["p99_ttft_s"] == 0.0
    assert st0["spec_rounds"] == 0 and st0["spec_depth_hist"] == {}
    prompts = _repetitive_prompts(cfg, (12, 16), seed=3)
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, tokens=list(p), max_new_tokens=8))
    eng.run(max_steps=300)
    warm = eng.stats()
    assert warm["requests"] == 2 and warm["spec_rounds"] > 0
    traces_before = dict(eng.trace_counts)
    eng.reset_stats()
    st1 = eng.stats()
    assert st1["requests"] == 0 and st1["decode_tokens"] == 0
    assert st1["prefill_tokens"] == 0 and st1["preemptions"] == 0
    assert st1["spec_rounds"] == 0 and st1["spec_proposed_tokens"] == 0
    assert st1["spec_accepted_tokens"] == 0
    assert st1["spec_depth_hist"] == {}
    for k in ("p50_ttft_s", "p99_ttft_s", "p50_tpot_s", "p99_tpot_s"):
        assert st1[k] == 0.0
    # singleton measured window (same footprint as the warm burst, so it
    # reuses its executables): percentiles degenerate to the sample
    eng.submit(Request(rid=9, tokens=list(prompts[0]), max_new_tokens=8))
    eng.run(max_steps=100)
    st2 = eng.stats()
    assert st2["requests"] == 1
    assert st2["p50_ttft_s"] == st2["p99_ttft_s"] > 0.0
    assert st2["decode_tokens"] == 7      # 8 output - 1 prefill token
    assert sum(st2["spec_depth_hist"].values()) == st2["spec_rounds"]
    # reset kept the compiled executables: no warm-window executable is
    # ever retraced (a previously-unseen narrow bucket may still compile)
    for key, n in traces_before.items():
        assert eng.trace_counts[key] == n
    assert all(n == 1 for n in eng.trace_counts.values())


def test_adaptive_depth_backoff_and_recovery():
    spec = Speculator(NGramProposer(), depth=8)
    req = _req([1], [2])
    req.spec_depth = 0
    assert spec.depth_for(req, budget=100) == 8
    # zero acceptance halves the depth down to the floor of 1
    for expect in (4, 2, 1, 1):
        spec.record(req, proposed=req.spec_depth, accepted=0)
        assert req.spec_depth == expect
    # full acceptance climbs back one per round, capped at the config
    for expect in (2, 3, 4, 5, 6, 7, 8, 8):
        spec.record(req, proposed=req.spec_depth, accepted=req.spec_depth)
        assert req.spec_depth == expect
    # partial acceptance settles just past the accepted run
    spec.record(req, proposed=8, accepted=3)
    assert req.spec_depth == 4
    st = spec.stats()
    assert st["spec_rounds"] == 13 and 0 < st["accept_rate"] < 1


def test_spec_respects_max_new_budget():
    """A fully-accepting proposer must not overshoot max_new_tokens."""
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = _params(cfg)
    eng_ref = Engine(cfg, params, max_batch=1, n_blocks=32, block_size=8)
    eng_ref.submit(Request(rid=0, tokens=list(range(1, 9)),
                           max_new_tokens=5))
    ref = eng_ref.run(max_steps=50)[0].output
    eng = Engine(cfg, params, max_batch=1, n_blocks=32, block_size=8,
                 speculate=ScriptedProposer(ref, good=8), spec_depth=8)
    eng.submit(Request(rid=0, tokens=list(range(1, 9)), max_new_tokens=5))
    done = eng.run(max_steps=50)
    assert done[0].output == ref and len(done[0].output) == 5


def test_engine_rejects_spec_with_legacy_mode():
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    with pytest.raises(ValueError):
        Engine(cfg, _params(cfg), mode="legacy", speculate="ngram")


def test_build_speculator_validation():
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    assert build_speculator(None, cfg) is None
    assert build_speculator("off", cfg) is None
    assert build_speculator("ngram", cfg).proposer.name == "ngram"
    with pytest.raises(ValueError):
        build_speculator("bogus", cfg)
    # different tokenizer/vocab (full configs: 151936 vs 50280)
    with pytest.raises(ValueError):
        build_speculator("draft:mamba2-130m",
                         get_config("qwen1.5-0.5b"))
    with pytest.raises(ValueError):
        Speculator(NGramProposer(), depth=0)
