"""Latency-SLO benchmark: Poisson arrivals against the serving engine.

Production serving is gated by *latency under contention* — TTFT/TPOT
percentiles when prompts are long and KV blocks run out — not by closed-loop
burst throughput (benchmarks/fig6_serving.py). This harness drives the
engine open-loop: a Poisson arrival process over a mixed prompt-length
trace. Per-scenario p50/p95/p99 TTFT and TPOT (plus throughput and
preemption counts) land in ``BENCH_latency.json`` so the scheduler's
tail-latency trajectory is tracked across PRs, the same way
BENCH_decode.json tracks the decode hot path.

Scenarios (smoke-scale honesty notes inline):
  * ``whole_prefill`` / ``chunked_prefill`` — steady state, every
    executable pre-built. At smoke scale (d_model 64) prompt FLOPs are
    negligible, so chunking shows its per-dispatch overhead rather than
    its head-of-line win; the structural numbers (queue time, tail order)
    still track the scheduler.
  * ``*_coldstart`` — the same trace on a fresh engine: the TTFT tail
    under a compile storm, a real production hazard for shape-specialized
    serving stacks. Whole-prompt prefill compiles one executable per
    (group size, prompt length) the trace discovers; chunked prefill
    compiles one chunk executable per block-table bucket — fewer
    executables, and since the chunk step reads its prefix through the
    paged multi-query kernel family (no dense per-layer page view in the
    graph anymore) each one is also cheaper to build than it was.
  * ``chunked_block_pressure`` — an undersized block pool with long
    generations: preemption fires and every request still completes; the
    TTFT/TPOT tails price the evictions.
  * ``whole_prefill_long`` / ``chunked_prefill_long`` — the chunk-prefill
    read-path rows: a long-prompt trace where the prefix grows to many
    table columns, exactly where the old dense (max_blocks*block) page
    view hurt. ``prefill_tok_s`` on these rows tracks the paged chunk
    read across PRs (the ``chunk_read_path`` field records which read the
    build used; PR <= 3 values were measured on the dense read).
  * ``deadline_storm`` — every request carries a tight wall-clock
    deadline under the same Poisson storm: the per-step sweep evicts
    expired requests as ``timed_out``, and the row records how many met
    the SLO vs. were shed. Every request still reaches a terminal state
    and every block returns to the pool — the graceful-degradation
    contract (engine "Failure semantics") priced as a benchmark row.
  * ``chunked_prefill_tp{N}`` — the chunked scenario on a model-axis-
    sharded engine (forced 8-device CPU mesh, one subprocess per degree
    on CPU via ``--model-parallel N`` so the device-count flag lands
    before jax initializes). On one physical socket these price the per-step GSPMD
    collective seam in the TTFT/TPOT tails — the scheduler behaves
    identically (host-global policy), so any tail shift is pure seam.
  * ``shared_prefix_nocache`` / ``shared_prefix_cache`` — the
    shared-system-prompt trace (every prompt opens with the same
    48-token prefix) with cross-request prefix caching off vs. on. The
    warm pass registers the prefix in the radix trie and
    ``reset_stats()`` keeps cache contents, so the measured pass serves
    every request from a warm cache: admission shares the cached blocks
    and prefill touches only the 8-token suffix. ``p50_ttft_hit_s``
    (TTFT over requests admitted with cached blocks) prices the skip
    against the whole-prefill ``p50_ttft_s`` of the nocache row.
  * ``shared_prefix_pool_nocache`` / ``shared_prefix_pool_cache`` — the
    same trace on a fixed undersized pool: without the cache each
    request owns its own copy of the prefix and the pool thrashes
    (preemption); with it the prefix is resident once and the freed
    blocks carry more concurrent decodes. The throughput / preemption
    columns at the *same* pool size are the goodput rows.
"""
import json
import os
import sys
import time

import jax

from benchmarks.common import emit, run_model_parallel_rows, \
    write_bench_json
from repro.configs import get_config
from repro.data.pipeline import (poisson_arrivals, serving_requests,
                                 shared_prefix_requests)
from repro.models.lm import LM
from repro.serving.engine import Engine, Request

N_REQUESTS = int(os.environ.get("BENCH_LATENCY_REQUESTS", 32))
RATE_RPS = float(os.environ.get("BENCH_LATENCY_RATE", 200.0))
PROMPT_LENS = (16, 64, 16, 32)      # mixed trace: short interactive + long
LONG_LENS = (32, 128, 64, 128)      # chunk-read stressor: many-column prefixes
MAX_NEW = 8
CHUNK = 16
# deadline_storm SLO: tight enough that the tail of a 200 rps burst on a
# max_batch-4 engine sheds load, loose enough that the head completes
DEADLINE_S = float(os.environ.get("BENCH_LATENCY_DEADLINE", 0.5))
OUT_PATH = os.environ.get("BENCH_LATENCY_JSON", "BENCH_latency.json")

ENGINE_KW = dict(max_batch=4, n_blocks=32, block_size=8)
PRESSURE_KW = dict(max_batch=4, n_blocks=12, block_size=8)
LONG_KW = dict(max_batch=4, n_blocks=96, block_size=8)
# shared-prefix trace: 6 prefix blocks + 1 suffix/decode tail per request.
# The fixed pool (16 blocks) fits ONE whole 8-block request copy-free;
# with the cache the prefix is resident once and 4 tails fit beside it.
SHARED_PREFIX_LEN = 48
SHARED_SUFFIX_LEN = 8
SHARED_POOL_KW = dict(max_batch=4, n_blocks=16, block_size=8)
TP_DEGREES = (2, 4)      # TP=1 is the plain chunked_prefill row
TP_FORCED_DEVICES = 8


def _drive(eng: Engine, prompts, arrivals, max_new: int) -> None:
    """Open-loop dispatch: submit each request at its arrival offset while
    stepping the engine; idle-wait when the queue is empty."""
    t0 = time.monotonic()
    i, n = 0, len(prompts)
    while True:
        now = time.monotonic() - t0
        while i < n and arrivals[i] <= now:
            eng.submit(Request(rid=i, tokens=list(prompts[i]),
                               max_new_tokens=max_new,
                               arrival=t0 + arrivals[i]))
            i += 1
        if eng.sched.has_work:
            eng.step()
        elif i < n:
            time.sleep(max(0.0, min(arrivals[i] - (time.monotonic() - t0),
                                    0.005)))
        else:
            break


def _warm_prefill_shapes(eng: Engine, cfg, max_new: int,
                         prompt_lens) -> None:
    """Build every whole-prefill executable the trace can demand: one
    grouped forward per (group size, prompt length) combination that
    admission could ever form (groups the block budget forbids here are
    forbidden identically during the measured pass)."""
    rid = 10_000
    for t in sorted(set(prompt_lens)):
        for g in range(1, eng.max_batch + 1):
            for p in serving_requests(g, cfg.vocab_size, prompt_len=t,
                                      seed=7):
                eng.submit(Request(rid=rid, tokens=p, max_new_tokens=max_new))
                rid += 1
            eng.run(max_steps=2000)


def _measure(cfg, params, *, prefill_chunk, warm=True, engine_kw=None,
             max_new=MAX_NEW, prompt_lens=PROMPT_LENS, mesh=None,
             deadline_s=None, prefix_cache=False, trace="mixed") -> dict:
    engine_kw = engine_kw or ENGINE_KW
    eng = Engine(cfg, params, prefill_chunk=prefill_chunk, mesh=mesh,
                 default_deadline_s=deadline_s, prefix_cache=prefix_cache,
                 **engine_kw)
    if trace == "shared":
        prompts = shared_prefix_requests(N_REQUESTS, cfg.vocab_size,
                                         prefix_len=SHARED_PREFIX_LEN,
                                         suffix_len=SHARED_SUFFIX_LEN,
                                         seed=0)
        prompt_lens = (SHARED_PREFIX_LEN + SHARED_SUFFIX_LEN,)
    else:
        prompts = serving_requests(N_REQUESTS, cfg.vocab_size, seed=0,
                                   prompt_lens=prompt_lens)
    arrivals = poisson_arrivals(N_REQUESTS, RATE_RPS, seed=1)
    if warm:
        eng.warmup(max(prompt_lens) + max_new,
                   prompt_lens=list(prompt_lens))
        if prefill_chunk is None:   # chunked engines never call _prefill_fwd
            _warm_prefill_shapes(eng, cfg, max_new, prompt_lens)
        # warm decode/chunk buckets; with prefix_cache on, this pass also
        # registers the trace's prefixes — reset_stats() keeps cache
        # contents, so the measured pass runs against a warm cache (the
        # production steady state the scenario prices)
        _drive(eng, prompts, arrivals, max_new)
        eng.reset_stats()
    _drive(eng, prompts, arrivals, max_new)      # measured pass
    # every request reaches a terminal state (timed_out counts as one)
    # and every block comes back: graceful degradation, not leakage.
    # Cached-but-unreferenced blocks count as available — capacity held
    # in the second-chance pool, one reclaim away from free.
    assert len(eng.finished) == N_REQUESTS
    assert eng.alloc.n_available == eng.alloc.n_blocks
    st = eng.stats()
    row = {
        "completed": int(st["requests"]),
        "finished": int(st["finished"]),
        "timed_out": int(st["timed_out"]),
        "throughput_tok_s": round(st["throughput_tok_s"], 2),
        "prefill_tok_s": round(st["prefill_tokens"]
                               / max(st["prefill_time_s"], 1e-9), 2),
        "p50_ttft_s": round(st["p50_ttft_s"], 5),
        "p95_ttft_s": round(st["p95_ttft_s"], 5),
        "p99_ttft_s": round(st["p99_ttft_s"], 5),
        "p50_tpot_s": round(st["p50_tpot_s"], 6),
        "p95_tpot_s": round(st["p95_tpot_s"], 6),
        "p99_tpot_s": round(st["p99_tpot_s"], 6),
        "mean_queue_s": round(st["mean_queue_s"], 5),
        "preemptions": int(st["preemptions"]),
    }
    if prefix_cache:
        # TTFT over cache-hit admissions only (requests that skipped
        # prefill via cached blocks) — compare against the nocache row's
        # p50_ttft_s, which prefills the whole prompt
        hit_ttfts = sorted(r.ttft() for r in eng.finished
                           if r.cached_tokens > 0 and r.ttft() is not None)
        row["p50_ttft_hit_s"] = (round(hit_ttfts[len(hit_ttfts) // 2], 5)
                                 if hit_ttfts else None)
        row["cache_hit_requests"] = len(hit_ttfts)
        row["prefix_cache_hit_rate"] = round(st["prefix_cache_hit_rate"], 3)
        row["cached_tokens_reused"] = int(st["cached_tokens_reused"])
        row["cached_blocks"] = int(st["cached_blocks"])
    return row


def _measure_telemetry_overhead(cfg, params) -> dict:
    """Telemetry-on vs. telemetry-off steady-state step cost on the warm
    chunked-prefill scenario (the observability contract row: hooks are
    host-side and guard on ``tel.enabled``, so the delta should stay in
    the noise — the issue budget is < 3%). ONE warm engine is measured
    with ``tel.enabled`` toggled between closed-loop passes: separate
    engines compile separate (identically-shaped) executables whose step
    times differ by a few percent for layout reasons alone, which would
    swamp the hook delta — toggling the flag on one engine runs the
    exact same compiled code both ways. Shared-host wall-clock noise
    dwarfs the delta at any whole-pass granularity (noise bursts are
    shorter than a pass), so the toggle happens PER STEP — adjacent
    steps share the noise regime — with the parity offset rotating per
    pass so every position in the (deterministic) step sequence is
    sampled both ways. The estimate is then PAIRED PER POSITION:
    min(on) vs. min(off) at each step index — pairing cancels step-kind
    mix (chunk vs. decode steps differ several-fold), and the min is
    the right location estimate here because scheduler noise is purely
    additive: the fastest of several samples of the same deterministic
    step is the closest observation of its intrinsic cost."""
    from repro.serving.telemetry import Telemetry

    prompts = serving_requests(N_REQUESTS, cfg.vocab_size, seed=0,
                               prompt_lens=PROMPT_LENS)
    arrivals = poisson_arrivals(N_REQUESTS, RATE_RPS, seed=1)
    eng = Engine(cfg, params, prefill_chunk=CHUNK, telemetry=Telemetry(),
                 **ENGINE_KW)
    eng.warmup(max(PROMPT_LENS) + MAX_NEW, prompt_lens=list(PROMPT_LENS))
    _drive(eng, prompts, arrivals, MAX_NEW)

    by_pos: dict = {}       # step index -> {False: [s, ...], True: [...]}
    for rep in range(10):
        eng.reset_stats()
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=30_000 + i, tokens=list(p),
                               max_new_tokens=MAX_NEW))
        i = 0
        while eng.sched.has_work:
            enabled = (i + rep) % 2 == 1
            eng.telemetry.enabled = enabled
            t0 = time.perf_counter()
            eng.step()
            by_pos.setdefault(i, {False: [], True: []})[enabled].append(
                time.perf_counter() - t0)
            i += 1
    eng.telemetry.enabled = True

    offs = [min(d[False]) for d in by_pos.values()]
    ons = [min(d[True]) for d in by_pos.values()]
    n = len(by_pos)
    off, on = sum(offs) / n * 1e3, sum(ons) / n * 1e3
    return {
        "step_ms_off": round(off, 4),
        "step_ms_on": round(on, 4),
        "overhead_pct": round(100.0 * (on - off) / off, 2),
    }


def _measure_model_parallel(tp: int) -> dict:
    """chunked_prefill scenario on a model-axis-sharded engine; on CPU it
    runs in a child process with the forced device count (see
    benchmarks.common.run_model_parallel_rows)."""
    from repro.launch.mesh import make_local_mesh
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    params = LM(cfg).init(jax.random.PRNGKey(0))
    mesh = make_local_mesh(model=tp, data=1) if tp > 1 else None
    r = _measure(cfg, params, prefill_chunk=CHUNK, mesh=mesh)
    r["model_parallel"] = tp
    r["devices"] = len(jax.devices())
    return r


def _run_tp_rows(results: dict) -> None:
    for tp, r in run_model_parallel_rows("benchmarks.bench_latency",
                                         _measure_model_parallel,
                                         TP_DEGREES, TP_FORCED_DEVICES):
        results["runs"][f"chunked_prefill_tp{tp}"] = r
        emit(f"bench_latency/chunked_prefill_tp{tp}",
             r["p95_ttft_s"] * 1e6,
             f"p50_ttft_s={r['p50_ttft_s']};p95_tpot_s={r['p95_tpot_s']};"
             f"tok_s={r['throughput_tok_s']};devices={r['devices']}")


def run():
    cfg = get_config("qwen1.5-0.5b", reduced=True)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    scenarios = {
        "whole_prefill": dict(prefill_chunk=None),
        "chunked_prefill": dict(prefill_chunk=CHUNK),
        "whole_prefill_coldstart": dict(prefill_chunk=None, warm=False),
        "chunked_prefill_coldstart": dict(prefill_chunk=CHUNK, warm=False),
        "chunked_block_pressure": dict(prefill_chunk=CHUNK,
                                       engine_kw=PRESSURE_KW, max_new=24),
        # SLO-deadline storm: tight deadlines shed the burst's tail
        "deadline_storm": dict(prefill_chunk=CHUNK, deadline_s=DEADLINE_S),
        # chunk-read stressors: long prefixes spanning many table columns
        "whole_prefill_long": dict(prefill_chunk=None,
                                   prompt_lens=LONG_LENS,
                                   engine_kw=LONG_KW),
        "chunked_prefill_long": dict(prefill_chunk=CHUNK,
                                     prompt_lens=LONG_LENS,
                                     engine_kw=LONG_KW),
        # shared-system-prompt trace: cache off = whole-prefill baseline,
        # cache on = every measured request admits with the prefix's 6
        # blocks shared and prefills only its 8-token suffix
        "shared_prefix_nocache": dict(prefill_chunk=CHUNK, trace="shared"),
        "shared_prefix_cache": dict(prefill_chunk=CHUNK, trace="shared",
                                    prefix_cache=True),
        # goodput at a fixed undersized pool: same 16-block pool, cache
        # off vs. on — the throughput/preemption columns are the rows
        "shared_prefix_pool_nocache": dict(prefill_chunk=CHUNK,
                                           trace="shared",
                                           engine_kw=SHARED_POOL_KW),
        "shared_prefix_pool_cache": dict(prefill_chunk=CHUNK,
                                         trace="shared",
                                         engine_kw=SHARED_POOL_KW,
                                         prefix_cache=True),
    }
    results = {
        "arch": cfg.name, "backend": jax.default_backend(),
        "rate_rps": RATE_RPS, "n_requests": N_REQUESTS,
        "prompt_lens": list(PROMPT_LENS), "long_prompt_lens": list(LONG_LENS),
        "max_new": MAX_NEW,
        "engine": dict(ENGINE_KW), "pressure_engine": dict(PRESSURE_KW),
        "long_engine": dict(LONG_KW),
        "shared_prefix": dict(prefix_len=SHARED_PREFIX_LEN,
                              suffix_len=SHARED_SUFFIX_LEN,
                              pool_engine=dict(SHARED_POOL_KW)),
        # which attention read the chunk step used this build: "paged"
        # (multi-query kernel family) since PR 4; "dense" through PR 3
        "chunk_read_path": "paged",
        "prefill_chunk": CHUNK, "deadline_s": DEADLINE_S, "runs": {},
    }
    for name, kw in scenarios.items():
        r = _measure(cfg, params, **kw)
        results["runs"][name] = r
        derived = (
            f"p50_ttft_s={r['p50_ttft_s']};p99_ttft_s={r['p99_ttft_s']};"
            f"p95_tpot_s={r['p95_tpot_s']};preempt={r['preemptions']};"
            f"tok_s={r['throughput_tok_s']};"
            f"prefill_tok_s={r['prefill_tok_s']};"
            f"finished={r['finished']};timed_out={r['timed_out']}")
        if "p50_ttft_hit_s" in r:
            derived += (f";p50_ttft_hit_s={r['p50_ttft_hit_s']};"
                        f"hit_rate={r['prefix_cache_hit_rate']};"
                        f"reused_tok={r['cached_tokens_reused']}")
        emit(f"bench_latency/{name}", r["p95_ttft_s"] * 1e6, derived)
    tel = _measure_telemetry_overhead(cfg, params)
    results["runs"]["telemetry_overhead"] = tel
    emit("bench_latency/telemetry_overhead", tel["step_ms_on"] * 1e3,
         f"step_ms_off={tel['step_ms_off']};step_ms_on={tel['step_ms_on']};"
         f"overhead_pct={tel['overhead_pct']}")
    _run_tp_rows(results)
    write_bench_json(OUT_PATH, results)


if __name__ == "__main__":
    if "--model-parallel" in sys.argv:
        tp = int(sys.argv[sys.argv.index("--model-parallel") + 1])
        print(json.dumps(_measure_model_parallel(tp)))
    else:
        print("name,us_per_call,derived")
        run()
