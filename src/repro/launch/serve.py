"""Serving launcher CLI: continuous-batching engine over synthetic bursts.

Fused decode, chunked prefill and speculative verify all read the KV
cache through ONE paged multi-query attention family
(kernels/flash_decode.paged_flash_prefix_partial): T query rows per
sequence share each page-tile fetch — the Pallas kernel on TPU, a
bounded column loop elsewhere — so every mode below exercises the same
read path at a different window width.

``--model-parallel N`` shards the whole engine over the ``model`` axis
of a local mesh (forcing N host devices on CPU when needed): parameters
partition through the same ShardCtx specs training uses, the paged
KV/SSM pools split on their head axes (each shard owns K/tp heads of
every page — writes, truncation and null-writes stay shard-local), and
every jitted step computes per-shard paged attention partials that
LSE-merge shard-locally, with the model-axis psum/all-gather surfacing
only at the row-parallel seams (wo, MLP down-proj, logits). The
scheduler and block accounting stay host-global — policy is
device-count-agnostic — and each engine step is still ONE dispatch.
On CPU, greedy output is token-identical to --model-parallel 1
(sharded dense contractions accumulate in f32, see models/layers.dense).
On a TPU, tokens whose logits are nearly tied can differ between the two
(the single-device engine reads attention through the Pallas kernel, the
sharded one through the XLA loop).

The arch runs at its published widths unless ``--reduced`` picks its CPU
smoke config, as every example below does.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --reduced --requests 16 --int8-kv    # fused jit decode (default)
    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --legacy                             # per-layer loop
    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --prefill-chunk 16                   # paged chunked prefill
    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --speculate ngram --spec-depth 8     # prompt-lookup speculation
    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --speculate draft:qwen1.5-0.5b       # draft-model speculation
    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --model-parallel 4                   # model-axis-sharded serving
    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --deadline-s 2.0 --queue-cap 8       # SLO deadlines + load shedding
    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --chaos 7                            # seeded fault injection
    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --prefix-cache --prefill-chunk 16    # cross-request prefix caching

``--prefix-cache`` turns on cross-request prefix caching
(serving/prefix_cache.py): full prefill blocks are content-indexed in a
radix trie, admission shares the longest cached prefix at refcount+1
(copy-on-write guards the tail), and refcount-zero cached blocks form an
LRU second-chance pool reclaimed only when the free list runs dry. A
trace with repeated prompts prefills each shared prefix once —
``prefix_cache_hit_rate`` and ``cached_tokens_reused`` in the printed
stats show the effect — while greedy output stays token-identical to a
cache-off run. Requires ``--prefill-chunk``: hits resume through the
chunk executable at chunk-aligned depths only, which is what makes the
parity exact rather than approximate.

Lifecycle flags (see the engine's "Failure semantics" docstring):
``--deadline-s`` stamps every request with a wall-clock deadline — the
engine's per-step sweep evicts expired requests as ``timed_out``;
``--queue-cap`` bounds the waiting queue so overload sheds load
(rejected requests are reported, not crashed on); ``--chaos <seed>``
wires a seeded deterministic FaultInjector (serving/faults.py) into the
run — block squeezes, forced allocator failures, delayed cancellations —
and prints the injection log plus per-cause terminal counts at the end.
"""
import argparse
from typing import List, Optional


def parse_mixed_lens(text: Optional[str]) -> Optional[List[int]]:
    """Parse ``--mixed-lens`` ("16,64,24") into prompt lengths, rejecting
    malformed input at the CLI boundary: empty entries ("16,,24"), junk
    tokens and non-positive lengths used to surface as a bare ValueError
    deep in ``int()`` — or worse, "0" built a degenerate empty-prompt
    request that the engine only rejects many layers later."""
    if text is None:
        return None
    lens: List[int] = []
    for tok in text.split(","):
        tok = tok.strip()
        if not tok:
            raise ValueError(
                f"--mixed-lens {text!r}: empty entry (double or trailing "
                f"comma?) — expected comma-separated positive ints")
        try:
            val = int(tok)
        except ValueError:
            raise ValueError(
                f"--mixed-lens {text!r}: {tok!r} is not an integer") \
                from None
        if val < 1:
            raise ValueError(
                f"--mixed-lens {text!r}: prompt length {val} must be >= 1")
        lens.append(val)
    return lens


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and validate the CLI; errors exit through argparse."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="the arch's CPU smoke config (d_model 64, vocab "
                         "256) instead of its published widths")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and of the prompts")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--n-blocks", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="page prompts out N tokens per step, interleaved "
                         "with decode (0 = whole-prompt prefill); the "
                         "chunk reads its paged prefix through the "
                         "multi-query kernel, no dense page view")
    ap.add_argument("--mixed-lens", default=None,
                    help="comma-separated prompt lengths cycled over the "
                         "burst, e.g. 16,64,24 (overrides --prompt-len)")
    ap.add_argument("--speculate", default="off",
                    help="speculative decoding proposer: off | ngram | "
                         "draft:<config> (draft shares the tokenizer; "
                         "smoke targets get smoke drafts)")
    ap.add_argument("--spec-depth", type=int, default=4,
                    help="max proposed tokens per verify round (adaptive "
                         "back-off may use less)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request wall-clock deadline in seconds; "
                         "expired requests are evicted as timed_out by "
                         "the per-step sweep (0 = no deadline)")
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="bound the waiting queue: submissions beyond the "
                         "cap are rejected (load shedding) instead of "
                         "queueing unboundedly (0 = unbounded)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request prefix caching: full prefill "
                         "blocks are content-indexed in a radix trie and "
                         "admission shares the longest cached prefix at "
                         "refcount+1, so repeated system prompts / "
                         "multi-turn histories prefill only their novel "
                         "suffix. Greedy output is token-identical to a "
                         "cache-off run; see prefix_cache_hit_rate / "
                         "cached_tokens_reused in the printed stats")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="seeded deterministic fault injection: block "
                         "squeezes, forced allocator failures and delayed "
                         "cancellations on a replayable schedule "
                         "(serving/faults.py)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable serving telemetry and write a Chrome-trace "
                         "JSON (chrome://tracing / Perfetto) of the run: "
                         "request-lifecycle spans, per-step phase events "
                         "and chaos actions on one timeline")
    ap.add_argument("--trace-fenced", action="store_true",
                    help="with --trace-out: block_until_ready-fence each "
                         "engine step so the per-step timeline charges "
                         "device time to the step that launched it "
                         "(perfscope semantics; adds sync overhead)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="shard the engine over a model-axis mesh of N "
                         "devices (params via ShardCtx specs, paged KV/SSM "
                         "pools on their head axes); forces N host devices "
                         "on CPU. On CPU, greedy output is token-identical "
                         "to N=1")
    grp = ap.add_mutually_exclusive_group()
    grp.add_argument("--fused", dest="mode", action="store_const",
                     const="fused", help="jit-compiled decode step (default)")
    grp.add_argument("--legacy", dest="mode", action="store_const",
                     const="legacy", help="per-layer Python decode loop")
    ap.set_defaults(mode="fused")
    args = ap.parse_args(argv)
    from repro.configs import list_archs
    if args.arch not in list_archs():
        ap.error(f"unknown --arch {args.arch!r} (choose from "
                 f"{', '.join(list_archs())})")
    try:
        args.lens = parse_mixed_lens(args.mixed_lens)
    except ValueError as e:
        ap.error(str(e))
    if args.chaos is not None and args.mode != "fused":
        ap.error("--chaos requires the fused engine (drop --legacy)")
    if args.prefix_cache and not args.prefill_chunk:
        ap.error("--prefix-cache requires --prefill-chunk N: a cache hit "
                 "resumes the suffix through the chunk executable, and "
                 "only a chunk-aligned resume keeps greedy output "
                 "token-identical to a cache-off run")
    if args.trace_fenced and not args.trace_out:
        ap.error("--trace-fenced requires --trace-out PATH")
    return args


def build_engine(args: argparse.Namespace):
    """Config, seeded random weights and the engine the flags describe.
    Nothing is compiled yet: see :func:`warm_up`."""
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.models.lm import LM
    from repro.serving.engine import Engine
    from repro.serving.faults import FaultInjector
    from repro.serving.telemetry import Telemetry

    mesh = (make_local_mesh(model=args.model_parallel, data=1)
            if args.model_parallel > 1 else None)
    cfg = get_config(args.arch, reduced=args.reduced)
    params = LM(cfg).init(jax.random.PRNGKey(args.seed))
    faults = (FaultInjector.from_seed(args.chaos,
                                      rids=range(args.requests))
              if args.chaos is not None else None)
    telemetry = Telemetry(enabled=bool(args.trace_out),
                          fenced=args.trace_fenced)
    return Engine(cfg, params, max_batch=args.max_batch,
                  n_blocks=args.n_blocks, block_size=args.block_size,
                  kv_quant="int8" if args.int8_kv else "none",
                  mode=args.mode,
                  prefill_chunk=args.prefill_chunk or None,
                  speculate=args.speculate, spec_depth=args.spec_depth,
                  mesh=mesh, queue_cap=args.queue_cap or None,
                  default_deadline_s=args.deadline_s or None,
                  faults=faults, prefix_cache=args.prefix_cache,
                  telemetry=telemetry)


def burst_prompts(args: argparse.Namespace, vocab: int) -> List[List[int]]:
    """The synthetic burst the flags describe, drawn from ``--seed``."""
    from repro.data.pipeline import serving_requests
    return serving_requests(args.requests, vocab,
                            prompt_len=args.prompt_len,
                            prompt_lens=args.lens, seed=args.seed)


def warm_up(eng, prompts: List[List[int]], max_new: int) -> None:
    """Compile every executable the burst will need before it arrives."""
    lens = sorted({len(p) for p in prompts})
    # warm every chunk-step table bucket the trace implies, not just the
    # widest: each distinct prompt length compiles its own footprint bucket
    # (a uniform trace still needs its prompt bucket, which can differ from
    # the max-footprint bucket warmup's max_seq_len argument implies)
    eng.warmup(max(lens) + max_new, prompt_lens=lens)


def serve_burst(eng, prompts: List[List[int]], max_new: int):
    """Submit the burst and run the engine until it drains. Returns the
    finished requests."""
    from repro.serving.engine import Rejected, Request

    for i, p in enumerate(prompts):
        try:
            eng.submit(Request(rid=i, tokens=list(p),
                               max_new_tokens=max_new))
        except Rejected as e:
            # load shedding is a reported outcome, not a launcher crash
            print(f"{'rejected':>20s}: rid={i} ({e.reason})")
    return eng.run()


def main(argv=None):
    """Serve one burst and print the stats. Returns the drained engine."""
    args = parse_args(argv)
    # before anything initializes the jax backend
    if args.model_parallel > 1:
        from repro.launch.mesh import ensure_host_devices
        ensure_host_devices(args.model_parallel)
    from repro.launch.jaxcache import use_compile_cache
    use_compile_cache()

    eng = build_engine(args)
    prompts = burst_prompts(args, eng.cfg.vocab_size)
    warm_up(eng, prompts, args.max_new)
    serve_burst(eng, prompts, args.max_new)
    if eng.faults is not None:
        eng.faults.release_all(eng)     # return any still-squeezed blocks
        # the injector mirrors every applied action into the telemetry
        # event log (faults._note), so the replay record printed here is
        # the same stream a --trace-out viewer sees on the chaos track
        for step, action, detail in eng.telemetry.chaos_actions:
            print(f"{'chaos':>20s}: step {step:>3d} {action} {detail}")
    print(f"{'mode':>20s}: {args.mode}")
    for k, v in eng.stats().items():
        print(f"{k:>20s}: {v:.4f}" if isinstance(v, float) else
              f"{k:>20s}: {v}")
    if args.mode == "fused":
        print(f"{'fused_step_traces':>20s}: {sum(eng.trace_counts.values())}")
    if args.trace_out:
        trace = eng.telemetry.export_chrome(
            args.trace_out,
            metadata={"arch": args.arch, "mode": args.mode,
                      "chaos_seed": args.chaos,
                      "model_parallel": args.model_parallel})
        print(f"{'trace_out':>20s}: {args.trace_out} "
              f"({len(trace['traceEvents'])} events)")
    return eng


if __name__ == "__main__":
    main()
