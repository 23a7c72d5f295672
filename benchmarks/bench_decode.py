"""Decode micro-benchmark: legacy per-layer loop vs fused jit step, plus
speculative-decoding scenarios.

Measures steady-state decode throughput (tok/s over the decode phase only)
at batch sizes 4 and 8 on the same burst workload, and writes
``BENCH_decode.json`` so the perf trajectory of the serving hot path is
tracked across PRs. Both paths get an unmeasured warmup burst first, so
jit compilation (fused) and eager op-cache compilation (legacy) are both
excluded from the timed window. CSV rows go through benchmarks/common.emit
like every other suite.

Speculative scenarios (batch 1 is the home turf — speculation is a
*low-batch latency* knob: it spends spare FLOPs to cut weight/KV reads
per token, so its win shrinks as batching fills the same per-step
forward; each ``spec_off_bs*`` row is the identical-workload baseline):

  * ``spec_ngram_bs1`` — n-gram/prompt-lookup proposer on a repetitive
    trace (a repeated 8-token pattern prompt; the greedy continuation of
    the smoke model is itself partially periodic, which is exactly the
    regime prompt lookup exploits). Acceptance rate is recorded; the
    speedup row is the PR's headline number.
  * ``spec_ngram_bs4`` / ``spec_off_bs4`` — the same trace at batch 4:
    the **bs>1 batched verify** rows. Every running request's window runs
    in ONE multi-token forward through the paged multi-query read (all T
    rows of a sequence share each page fetch), so these rows track the
    ROADMAP item of making batched verify pay past its bs1 sweet spot.
  * ``spec_draft_self_bs1`` — draft-model proposer drafting with the
    *target's own* params ("qwen-smoke" self-draft): acceptance is 1.0 by
    construction, isolating the verify-path mechanics. Honesty note: at
    smoke scale the draft loop itself runs eagerly (one prefill + K-1
    decode dispatches per round), so wall-clock is dominated by the
    proposer, not the verify forward — the recorded value tracks that
    overhead until the draft gets its own jitted cache (ROADMAP).

Model-parallel rows (``tp{N}_bs4``): the fused decode scenario sharded
over a forced 8-device CPU mesh at TP in {1, 2, 4, 8} — on CPU each
degree runs in a fresh subprocess (``--model-parallel N`` on this
module) because ``--xla_force_host_platform_device_count`` must be set
before the jax backend initializes, and forcing it in the parent would
distort the single-device rows. On CPU smoke these rows measure the *sharding seam
overhead* (GSPMD psum/all-gather per step on one physical socket), not a
speedup: smoke-scale math is far below the collective launch cost, so
tok/s drops as TP rises. The row the TPU deployment cares about is that
the one-dispatch-per-step contract and token parity hold at every degree.
"""
import json
import os
import sys

import jax
import numpy as np

from benchmarks.common import emit, run_model_parallel_rows, \
    write_bench_json
from repro.configs import get_config
from repro.data.pipeline import repetitive_requests, serving_requests
from repro.models.lm import LM
from repro.serving.engine import Engine, Request
from repro.serving.speculate import DraftModelProposer

PROMPT_LEN = 24
MAX_NEW = 8
SPEC_PROMPT_LEN = 24
SPEC_MAX_NEW = 128
SPEC_REQUESTS = 6        # 1 unmeasured warmup + 5 measured
SPEC_PATTERN_SEED = 2
TP_DEGREES = (1, 2, 4, 8)
TP_FORCED_DEVICES = 8
OUT_PATH = os.environ.get("BENCH_DECODE_JSON", "BENCH_decode.json")


def _measure(cfg, params, *, max_batch: int, mode: str, mesh=None) -> dict:
    eng = Engine(cfg, params, max_batch=max_batch, n_blocks=64,
                 block_size=8, mode=mode, mesh=mesh)
    eng.warmup(PROMPT_LEN + MAX_NEW)
    prompts = serving_requests(3 * max_batch, cfg.vocab_size,
                               prompt_len=PROMPT_LEN, seed=0)
    # warmup burst: compiles the fused step / legacy eager op caches for
    # every table shape the measured burst will see
    for i, p in enumerate(prompts[:max_batch]):
        eng.submit(Request(rid=i, tokens=p, max_new_tokens=MAX_NEW))
    eng.run(max_steps=2000)
    tok0, time0 = eng.decode_tokens, eng.decode_time
    # measured burst
    for i, p in enumerate(prompts[max_batch:]):
        eng.submit(Request(rid=max_batch + i, tokens=p,
                           max_new_tokens=MAX_NEW))
    eng.run(max_steps=2000)
    toks = eng.decode_tokens - tok0
    secs = eng.decode_time - time0
    return {
        "decode_tok_s": round(toks / max(secs, 1e-9), 2),
        "decode_tokens": int(toks),
        "decode_time_s": round(secs, 4),
        "fused_step_traces": (sum(eng.trace_counts.values())
                              if mode == "fused" else None),
    }


def _measure_spec(cfg, params, *, speculate, spec_depth: int,
                  max_new: int, n_requests: int = 3, max_batch: int = 1,
                  n_warm: int = 1) -> dict:
    from collections import Counter

    eng = Engine(cfg, params, max_batch=max_batch, n_blocks=512,
                 block_size=8, speculate=speculate, spec_depth=spec_depth)
    eng.warmup(SPEC_PROMPT_LEN + max_new)
    prompts = repetitive_requests(n_requests, cfg.vocab_size,
                                  prompt_len=SPEC_PROMPT_LEN,
                                  seed=SPEC_PATTERN_SEED)
    # warmup burst (one full batch): compiles every (window, table)
    # bucket the measured trace can use
    for i, p in enumerate(prompts[:n_warm]):
        eng.submit(Request(rid=i, tokens=list(p), max_new_tokens=max_new))
    eng.run(max_steps=8000)
    tok0, time0 = eng.decode_tokens, eng.decode_time
    sp0, sa0 = ((eng.spec.proposed_tokens, eng.spec.accepted_tokens)
                if eng.spec else (0, 0))
    hist0 = Counter(eng.spec.depth_hist) if eng.spec else Counter()
    for i, p in enumerate(prompts[n_warm:], start=n_warm):
        eng.submit(Request(rid=i, tokens=list(p), max_new_tokens=max_new))
    eng.run(max_steps=8000)
    toks = eng.decode_tokens - tok0
    secs = eng.decode_time - time0
    out = {
        "decode_tok_s": round(toks / max(secs, 1e-9), 2),
        "decode_tokens": int(toks),
        "decode_time_s": round(secs, 4),
    }
    if eng.spec is not None:
        prop = eng.spec.proposed_tokens - sp0
        acc = eng.spec.accepted_tokens - sa0
        out["proposed_tokens"] = int(prop)
        out["accepted_tokens"] = int(acc)
        out["accept_rate"] = round(acc / max(prop, 1), 4)
        # measured burst only, consistent with the counters above
        hist = eng.spec.depth_hist - hist0
        out["spec_depth_hist"] = {str(k): v
                                  for k, v in sorted(hist.items())}
    return out


def _measure_model_parallel(tp: int) -> dict:
    """One TP row, run by ``run_model_parallel_rows``: in a child process
    with the CPU device count forced, or in this process on a chip host.
    Token parity with TP=1 is pinned by
    tests/test_sharded_serving.py; this row records the throughput cost of
    the sharding seam at each degree."""
    from repro.launch.mesh import make_local_mesh
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = LM(cfg).init(jax.random.PRNGKey(0))
    mesh = make_local_mesh(model=tp, data=1) if tp > 1 else None
    r = _measure(cfg, params, max_batch=4, mode="fused", mesh=mesh)
    r["model_parallel"] = tp
    r["devices"] = len(jax.devices())
    return r


def _run_tp_rows(results: dict) -> None:
    for tp, r in run_model_parallel_rows("benchmarks.bench_decode",
                                         _measure_model_parallel,
                                         TP_DEGREES, TP_FORCED_DEVICES):
        results["runs"][f"tp{tp}_bs4"] = r
        emit(f"bench_decode/tp{tp}_bs4", r["decode_time_s"] * 1e6,
             f"decode_tok_s={r['decode_tok_s']};devices={r['devices']}")


def run(spec_depth: int = 8):
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    results = {"arch": cfg.name, "backend": jax.default_backend(),
               "prompt_len": PROMPT_LEN, "max_new": MAX_NEW,
               "spec_depth": spec_depth, "runs": {}}
    for bs in (4, 8):
        for mode in ("legacy", "fused"):
            r = _measure(cfg, params, max_batch=bs, mode=mode)
            results["runs"][f"{mode}_bs{bs}"] = r
            emit(f"bench_decode/{mode}_bs{bs}",
                 r["decode_time_s"] * 1e6,
                 f"decode_tok_s={r['decode_tok_s']}")
        legacy = results["runs"][f"legacy_bs{bs}"]["decode_tok_s"]
        fused = results["runs"][f"fused_bs{bs}"]["decode_tok_s"]
        results["runs"][f"speedup_bs{bs}"] = round(fused / max(legacy, 1e-9),
                                                   2)
        emit(f"bench_decode/speedup_bs{bs}", 0,
             f"{results['runs'][f'speedup_bs{bs}']}x_fused_over_legacy")
    # --- speculative scenarios (see module docstring) ---
    scenarios = {
        "spec_off_bs1": dict(speculate=None, max_new=SPEC_MAX_NEW,
                             n_requests=SPEC_REQUESTS),
        "spec_ngram_bs1": dict(speculate="ngram", max_new=SPEC_MAX_NEW,
                               n_requests=SPEC_REQUESTS),
        # bs>1 batched verify: a full batch of windows per verify forward
        "spec_off_bs4": dict(speculate=None, max_new=SPEC_MAX_NEW,
                             n_requests=12, max_batch=4, n_warm=4),
        "spec_ngram_bs4": dict(speculate="ngram", max_new=SPEC_MAX_NEW,
                               n_requests=12, max_batch=4, n_warm=4),
        "spec_draft_self_bs1": dict(
            speculate=DraftModelProposer(cfg, params), max_new=16,
            n_requests=2),
    }
    for name, kw in scenarios.items():
        r = _measure_spec(cfg, params, spec_depth=spec_depth, **kw)
        results["runs"][name] = r
        emit(f"bench_decode/{name}", r["decode_time_s"] * 1e6,
             f"decode_tok_s={r['decode_tok_s']}"
             + (f";accept_rate={r['accept_rate']}"
                if "accept_rate" in r else ""))
    for bs_tag in ("bs1", "bs4"):
        base = results["runs"][f"spec_off_{bs_tag}"]["decode_tok_s"]
        ngram = results["runs"][f"spec_ngram_{bs_tag}"]["decode_tok_s"]
        results["runs"][f"speedup_spec_ngram_{bs_tag}"] = round(
            ngram / max(base, 1e-9), 2)
        emit(f"bench_decode/speedup_spec_ngram_{bs_tag}", 0,
             f"{results['runs'][f'speedup_spec_ngram_{bs_tag}']}"
             "x_ngram_over_plain")
    # --- model-parallel rows (benchmarks.common.run_model_parallel_rows) ---
    _run_tp_rows(results)
    write_bench_json(OUT_PATH, results)


if __name__ == "__main__":
    if "--model-parallel" in sys.argv:
        tp = int(sys.argv[sys.argv.index("--model-parallel") + 1])
        print(json.dumps(_measure_model_parallel(tp)))
    else:
        print("name,us_per_call,derived")
        run()
