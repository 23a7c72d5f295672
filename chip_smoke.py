"""Chip smoke test: serve qwen1.5-0.5b at its published widths on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # TP=4 engine against TP=1, four chips

The engine is built through ``launch/serve.py`` (``build_engine``,
``warm_up``, ``serve_burst``) with random weights from ``--seed`` and
chunked prefill. One chip runs three passes over 8 requests of a few
hundred prompt tokens and 32 new tokens each: bf16 KV and int8 KV on
mixed prompt lengths (the decode and chunk steps, with the Pallas paged
kernel), and n-gram speculation on repeated-pattern prompts (the verify
step). ``--chips 4`` runs only the model-parallel engine over
``make_local_mesh(model=4, data=1)`` and the TP=1 engine on device 0 it
is compared with. Both must pass the reference check below; where their
greedy tokens part, the script prints how close the reference scores the
two choices (random weights give nearly flat logits, so near-ties are
common).

Each pass checks that every request finished with finite logits, that no
executable was traced after warm-up, and that every emitted token is the
argmax, within :data:`TOL_SIGMA`, of a plain float32 forward of the same
model over prompt plus output, run on the chip. The script prints pool
sizes, compile seconds, trace counts and peak device memory on earlier
lines (never a speed), and as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
It exits non-zero, with no such line, when JAX finds no TPU or any check
fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
BLOCK = 16
CHUNK = 128
MAX_BATCH = 8
MAX_NEW = 32
SPEC_DEPTH = 4
# every prompt spans more than 256 tokens and prompt + output stays within
# 512, so decode, chunk and verify each use the one 32-column table bucket
# that warm-up compiles
PROMPT_LENS = (300, 417, 262, 389, 341, 455, 280, 366)
SPEC_PROMPT_LEN = 320
# pools tried largest first; the first whose decode step fits is served
POOL_BLOCKS = (1024, 512, 256)
# share of the device's memory a pool's decode step may plan for; the
# rest covers the live pool beside warm-up's copy and the other steps
MEM_SHARE = 0.8
# An emitted token passes when its reference logit is within TOL_SIGMA
# standard deviations (over the vocabulary, at that position) of the
# reference maximum. bf16 activations and logits drift by a few hundredths
# of a deviation over 24 layers; a token from a wrong read sits
# several deviations below the maximum.
TOL_SIGMA = 0.5


def serve_args(seed: int, n_blocks: int, *extra: str):
    from repro.launch import serve
    return serve.parse_args([
        "--arch", ARCH, "--seed", str(seed), "--requests", str(MAX_BATCH),
        "--max-new", str(MAX_NEW), "--max-batch", str(MAX_BATCH),
        "--n-blocks", str(n_blocks), "--block-size", str(BLOCK),
        "--prefill-chunk", str(CHUNK), "--spec-depth", str(SPEC_DEPTH),
        "--mixed-lens", ",".join(map(str, PROMPT_LENS)), *extra])


def compile_decode_step(eng, n_blocks: int, sharding):
    """Compile ``eng``'s fused decode step for a pool of ``n_blocks``
    blocks at the smoke's table bucket, every argument placed by
    ``sharding``, without allocating the pool."""
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    mbb = -(-(max(PROMPT_LENS) + MAX_NEW) // BLOCK)
    mbb = 1 << (mbb - 1).bit_length()
    b = eng.max_batch
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), eng.params)
    kv = {k: sds((a.shape[0], n_blocks) + tuple(a.shape[2:]), a.dtype)
          for k, a in eng.kv.state.items()}
    return eng._fused_step.lower(
        params, kv, {}, sds((b,), jnp.int32), sds((b,), jnp.int32),
        sds((b, mbb), jnp.int32), sds((b,), jnp.bool_),
        sds((eng.model.n_periods, b), jnp.bool_)).compile()


def step_bytes(compiled) -> int:
    """Device bytes a compiled step plans for: arguments, outputs and
    temporaries, less the donated buffers its outputs reuse."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def fit_pool(seed: int, extra, device) -> int:
    """The largest of :data:`POOL_BLOCKS` whose decode step, plus the
    live pool, fits :data:`MEM_SHARE` of the device's memory, from the
    compiler's ``memory_analysis()`` of that step."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.launch import serve

    limit = device.memory_stats()["bytes_limit"]
    probe = serve.build_engine(serve_args(seed, 2 * MAX_BATCH, *extra))
    per_block = probe.kv.hbm_bytes() // probe.kv_cfg.n_blocks
    try:
        for n in POOL_BLOCKS:
            try:
                need = step_bytes(compile_decode_step(
                    probe, n, SingleDeviceSharding(device)))
            except jax.errors.JaxRuntimeError as e:   # refused: too big
                print(f"  pool {n} blocks: refused ({str(e)[:120]})")
                continue
            print(f"  pool {n} blocks: decode step plans {need} B, live "
                  f"pool {per_block * n} B, device limit {limit} B")
            if need + per_block * n <= MEM_SHARE * limit:
                return n
    finally:
        release(probe)
    raise RuntimeError(f"no pool of {POOL_BLOCKS} blocks fits {device}")


def release(eng) -> None:
    """Free an engine's device buffers now (the jit caches may keep the
    engine object itself alive)."""
    import jax
    for a in jax.tree.leaves((eng.params, eng.kv.state, eng._ssm_states)):
        a.delete()
    gc.collect()


def run_pass(name: str, eng, prompts):
    """Warm up, serve the burst, and check what the engine itself can
    show. Returns {rid: (prompt, output)}."""
    from repro.launch import serve
    from repro.serving.scheduler import FINISHED

    t0 = time.perf_counter()
    serve.warm_up(eng, prompts, MAX_NEW)
    compile_s = time.perf_counter() - t0
    warmed = dict(eng.trace_counts)
    done = serve.serve_burst(eng, prompts, MAX_NEW)
    traces = dict(eng.trace_counts)
    print(f"[{name}] blocks={eng.kv_cfg.n_blocks}x{BLOCK} "
          f"pool_bytes={eng.kv.hbm_bytes()} compile_s={compile_s:.1f} "
          f"steps={eng.steps} trace_counts={traces}")
    if traces != warmed:
        raise RuntimeError(f"[{name}] traced after warm-up: {warmed} -> "
                           f"{traces}")
    # a non-finite logit row is evicted as FAILED by the in-jit flag
    bad = [(r.rid, r.state) for r in done if r.state != FINISHED]
    if len(done) != len(prompts) or bad or any(
            len(r.output) != MAX_NEW for r in done):
        raise RuntimeError(f"[{name}] not every request finished: {bad}")
    return {r.rid: (list(r.tokens), list(r.output)) for r in done}


def check_pallas_decode(name: str, eng, device) -> None:
    """The compiled decode step must hold the Pallas kernel."""
    from jax.sharding import SingleDeviceSharding
    text = compile_decode_step(eng, eng.kv_cfg.n_blocks,
                               SingleDeviceSharding(device)).as_text()
    if "tpu_custom_call" not in text:
        raise RuntimeError(f"[{name}] decode step has no tpu_custom_call")


class Reference:
    """Plain float32 forward of the model (``LM.forward``, naive
    attention, highest matmul precision) on the chip."""

    def __init__(self, cfg, params):
        import jax
        import jax.numpy as jnp
        from repro.models.lm import LM

        self.params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        self.length = max(PROMPT_LENS) + MAX_NEW
        model = LM(cfg)
        self._fwd = jax.jit(
            lambda p, t: model.forward(p, {"tokens": t})[0])

    def rows(self, prompt, output):
        """Reference logits at the positions that predict ``output``."""
        import jax
        import numpy as np

        seq = prompt + output[:-1]
        toks = np.zeros((1, self.length), np.int32)
        toks[0, :len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            logits = self._fwd(self.params, toks)
        return np.asarray(logits[len(prompt) - 1:len(seq)])

    def check(self, name: str, results) -> None:
        import numpy as np

        worst, exact, total = 0.0, 0, 0
        for rid, (prompt, output) in sorted(results.items()):
            rows = self.rows(prompt, output)
            if not np.isfinite(rows).all():
                raise RuntimeError(f"[{name}] rid {rid}: reference logits "
                                   f"not finite")
            got = rows[np.arange(len(output)), output]
            gap = (rows.max(-1) - got) / rows.std(-1)
            worst = max(worst, float(gap.max()))
            exact += int((rows.argmax(-1) == np.asarray(output)).sum())
            total += len(output)
            if gap.max() > TOL_SIGMA:
                i = int(gap.argmax())
                raise RuntimeError(
                    f"[{name}] rid {rid} token {i}: {output[i]} is "
                    f"{gap[i]:.3f} sd below the float32 argmax "
                    f"{int(rows[i].argmax())} (tolerance {TOL_SIGMA})")
        print(f"[{name}] reference: {exact}/{total} tokens are the float32 "
              f"argmax; worst gap {worst:.4f} sd (tolerance {TOL_SIGMA})")


def peak_line(name: str, devices) -> None:
    print(f"[{name}] peak_bytes_in_use="
          f"{[d.memory_stats()['peak_bytes_in_use'] for d in devices]}")


def one_chip(seed: int, device) -> None:
    from repro.data.pipeline import repetitive_requests
    from repro.launch import serve

    ref = None
    sizes = {}
    passes = (("bf16", ()), ("int8", ("--int8-kv",)),
              ("spec-ngram", ("--speculate", "ngram")))
    for name, extra in passes:
        kv = "int8" if "--int8-kv" in extra else "bf16"
        if kv not in sizes:
            sizes[kv] = fit_pool(seed, extra, device)
        args = serve_args(seed, sizes[kv], *extra)
        if name == "spec-ngram":
            prompts = repetitive_requests(MAX_BATCH, _vocab(),
                                          prompt_len=SPEC_PROMPT_LEN,
                                          seed=seed)
        else:
            prompts = serve.burst_prompts(args, _vocab())
        eng = serve.build_engine(args)
        results = run_pass(name, eng, prompts)
        if eng.spec is None:
            check_pallas_decode(name, eng, device)
        else:
            print(f"[{name}] spec: {eng.spec.stats()}")
        if ref is None:
            ref = Reference(eng.cfg, eng.params)
        release(eng)
        ref.check(name, results)
        peak_line(name, [device])


def check_spread(eng, devices) -> None:
    """The TP engine's pool and weights must sit on every device, split."""
    from collections import Counter
    import jax

    k = eng.kv.state["k"]         # (L, n_blocks, block, K*hd)
    hd = eng.kv_cfg.head_dim
    heads = {s.device: s.data.shape[3] // hd for s in k.addressable_shards}
    if set(heads) != set(devices) or set(heads.values()) != {
            eng.kv_cfg.n_kv_heads // len(devices)}:
        raise RuntimeError(f"kv pool {k.shape} not split over the kv "
                           f"heads of {len(devices)} devices: {heads}")
    # the tied embedding and the norms stay whole on every device by
    # design (parallel/sharding.py); every other weight is split n ways
    held = Counter()
    leaves = jax.tree.leaves(eng.params)
    for a in leaves:
        for s in a.addressable_shards:
            held[s.device] += s.data.nbytes
    whole = sum(a.nbytes for a in leaves if a.sharding.is_fully_replicated)
    split = sum(a.nbytes for a in leaves) - whole
    expect = whole + split // len(devices)
    print(f"[tp4] kv heads per device {sorted(heads.values())}; weight "
          f"bytes per device {sorted(held.values())}: {whole} replicated "
          f"+ {split} split {len(devices)} ways")
    if (set(held) != set(devices) or split == 0
            or set(held.values()) != {expect}):
        raise RuntimeError(f"weights are not split over the devices: "
                           f"{held}, expected {expect} B each")


def four_chips(seed: int, devices) -> None:
    from repro.launch import serve

    # 8 requests of at most 32 blocks each: half the one-chip pool holds
    # them all without preemption
    args = serve_args(seed, POOL_BLOCKS[1])
    prompts = serve.burst_prompts(args, _vocab())
    eng = serve.build_engine(args)
    tp1 = run_pass("tp1", eng, prompts)
    ref = Reference(eng.cfg, eng.params)
    release(eng)
    ref.check("tp1", tp1)

    eng = serve.build_engine(
        serve_args(seed, POOL_BLOCKS[1], "--model-parallel", "4"))
    tp4 = run_pass("tp4", eng, prompts)
    check_spread(eng, devices)
    release(eng)
    ref.check("tp4", tp4)
    same = 0
    for rid, (prompt, out1) in sorted(tp1.items()):
        out4 = tp4[rid][1]
        if out1 == out4:
            same += 1
            continue
        # both runs agree up to token i, so both choices there were made
        # on the same context: show how close the reference scores them
        i = next(j for j, (a, b) in enumerate(zip(out1, out4)) if a != b)
        row = ref.rows(prompt, out4)[i]
        print(f"[tp4] rid {rid} first differs at token {i}: tp1 {out1[i]} "
              f"tp4 {out4[i]}, float32 logits {row[out1[i]]:.6f} vs "
              f"{row[out4[i]]:.6f}, max {row.max():.6f}, sd {row.std():.6f}")
    print(f"[tp4] greedy tokens identical to tp1 for {same}/{len(tp1)} "
          f"requests")
    peak_line("tp4", devices)


def _vocab() -> int:
    from repro.configs import get_config
    return get_config(ARCH).vocab_size


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.launch.jaxcache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {devices[0].device_kind} x{len(devices)}, jax "
          f"{jax.__version__}")
    if args.chips == 1:
        one_chip(args.seed, devices[0])
    else:
        four_chips(args.seed, devices[:4])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
