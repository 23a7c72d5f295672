"""Chip benchmark of the serving engine, one cell per run.

    python bench/run.py --workload qwen05b-chat-poisson --seed 7 \
        --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a model configuration
(``bench/configs/``), whose family (bench/families/) builds and counts the
model and gives its reference, and a traffic mix (``bench/traffic/``).
One process:

1. turns on JAX's persistent compilation cache at ``.jax_cache/`` in the
   checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says);
2. finds the chips the cell asks for, or exits non-zero with no result;
3. makes the weights from ``--seed`` on the device (bench/model.py);
4. fits the KV pool to the memory the decode and chunk steps and the
   recurrent state leave;
5. compiles the decode and chunk steps at every block-table width the
   cell's lengths can reach, and no others;
6. ramps the traffic for the mix's ``ramp_s``, then measures ``--seconds``
   (bench/driver.py), with the profiler over the window's last seconds
   when ``--trace 1``;
7. reads peak device memory, frees the engine's device state, and holds a
   sample of the finished requests to the family's float32 reference
   (bench/correct.py);
8. prints the numbers compared beside their limits on standard error,
   and one JSON line on standard output: ``correct``, ``attempted``,
   ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
   ``--trace 1`` its per-layer metrics), ``device``, ``breakdown`` (with
   ``--trace 1``) and ``checks``.

``setup_s`` runs from the process's start to the window's start: loading,
compiling, fitting the pool and the ramp.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Optional  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

CACHE_DIR = REPO / ".jax_cache"
# the profiler covers the window's last TRACE_S seconds, or TRACE_S_SHARDED
# where the cell runs on several chips: there it records every chip's ops,
# and the sharded steps run some 60k ops a step a chip (the XLA paged read),
# so on four v5e chips 1 s of trace took 95 s to stop (224 MB)
TRACE_S, TRACE_S_SHARDED = 4.0, 0.5


class NoChip(RuntimeError):
    pass


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str, root: Path = REPO):
    """(benchmark, workload, configuration, traffic mix) of a cell. A
    configuration built for another number of chips than the cell asks
    for is an error."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    conf = json.loads((root / cfg["file"]).read_text())
    if int(conf["engine"]["chips"]) != int(wl["chips"]):
        raise ValueError(f"{name}: configuration {cfg['name']} is built for "
                         f"{conf['engine']['chips']} chips, the cell asks "
                         f"for {wl['chips']}")
    mix = json.loads((root / "bench" / "traffic" /
                      f"{wl['traffic']}.json").read_text())
    return bench, wl, conf, mix


def compile_cache() -> str:
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def chips(n: int):
    """The first ``n`` chips, or ``NoChip``. A cell on several chips builds
    its engine with the host as the default device (bench/model.py
    build_engine), so there the host's backend is kept on beside them."""
    import jax
    platforms = jax.config.jax_platforms
    if n > 1 and platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


@dataclasses.dataclass
class Context:
    """What the per-layer readers see (bench/layer_metrics)."""
    run: object
    es: dict
    counts: object
    peaks: dict
    chips: int
    trace: Optional[dict]
    compiles: int
    peak_bytes: Optional[int]
    n_blocks: int
    notes: dict = dataclasses.field(default_factory=dict)


class CompileCounter:
    """JAX's compile events (a build or a cache load of an executable)
    while ``armed``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.n += 1


def peak_bytes(devices) -> Optional[int]:
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        return None
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


@dataclasses.dataclass
class Prepared:
    eng: object
    params: dict
    es: dict
    cfg: object
    n_blocks: int
    widths: list
    seed: int
    shardings: object = None    # of the weights, on more than one chip


def prepare(conf, mix, seed: int, devices, *, clock=time.monotonic,
            out=log) -> Prepared:
    """Weights from the seed, the fitted pool, and an engine with every
    table width the cell's lengths can reach compiled. On more than one
    chip the engine is the program's tensor-parallel one, on a mesh over
    ``devices``, and each weight is drawn into the sharding it serves in."""
    import jax

    from bench import families, model, traffic

    es = conf["engine"]
    cfg = families.load(conf).arch_config(conf)
    mesh = model.make_mesh(devices) if len(devices) > 1 else None
    shardings = None if mesh is None else model.param_shardings(cfg, mesh)
    params = model.make_weights(conf, seed, shardings)
    model.check_layout(cfg, params)
    jax.block_until_ready(params)
    held = model.bytes_per_chip(params)
    out(f"weights: {sum(a.nbytes for a in jax.tree.leaves(params))} bytes, "
        f"per chip {sorted(held.values(), reverse=True)}")
    lo = -(-int(mix["prompt_len"]["min"]) // es["block_size"])
    widths = [w for w in model.buckets(traffic.max_context(mix),
                                       es["block_size"])
              if w >= 1 << (lo - 1).bit_length()]
    n_blocks, _ = model.fit_pool(cfg, params, es, widths[-1], devices, out,
                                 mesh)
    eng = model.build_engine(cfg, params, es, n_blocks, mesh)
    t = clock()
    model.warm(eng, widths)
    out(f"warm-up: widths {widths}, {clock() - t:.1f} s, "
        f"trace_counts {dict(eng.trace_counts)}")
    return Prepared(eng, params, es, cfg, n_blocks, widths, seed, shardings)


def run_cell(bench, wl, conf, mix, *, seed: int, seconds: float, trace: bool,
             devices, peaks: dict, limits: dict, t_start: float,
             clock: Callable[[], float] = time.monotonic, out=log) -> dict:
    import jax

    from bench import correct, driver, families, model, trace_reduce, \
        traffic, window
    from bench.layer_metrics import load as load_metric

    prep = prepare(conf, mix, seed, devices, clock=clock, out=out)
    eng, params, es, cfg = prep.eng, prep.params, prep.es, prep.cfg
    family = families.load(conf)
    counts, n_blocks = family.counts(conf, es), prep.n_blocks
    plan = traffic.plan(mix, seed, seconds, cfg.vocab_size)
    counter = CompileCounter()

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tr_arg = None
    if trace:
        def start_fn():
            jax.profiler.start_trace(tdir)
        def stop_fn():
            t = clock()
            jax.profiler.stop_trace()
            out(f"trace: stopped in {clock() - t:.1f} s")
        traced_s = TRACE_S if len(devices) == 1 else TRACE_S_SHARDED
        tr_arg = (min(traced_s, seconds), start_fn, stop_fn)

    start = clock()
    run = driver.drive(eng, plan, start, float(mix["ramp_s"]), seconds,
                       clock=clock, trace=tr_arg,
                       on_window=lambda: setattr(counter, "armed", True))
    counter.armed = False
    setup_s = run.t0 - t_start
    e2e = window.end_to_end(run.tracked, run.t0, run.t1)
    e2e["setup_s"] = setup_s
    peak = peak_bytes(devices)

    reduced = None
    if trace:
        t = clock()
        path = trace_reduce.find(tdir)
        size = os.path.getsize(path)
        reduced = trace_reduce.read(path)
        shutil.rmtree(tdir, ignore_errors=True)
        out(f"trace: {size} bytes, {len(reduced['ops'])} op names, read in "
            f"{clock() - t:.1f} s")

    due = [tr for tr in run.tracked if run.t0 <= tr.due < run.t1]
    failed = sum(tr.state in ("failed", "rejected", "timed_out", "cancelled")
                 for tr in due)
    ctx = Context(run, es, counts, peaks, len(devices), reduced, counter.n,
                  peak, n_blocks)
    win = [s for s in run.steps if run.t0 <= s.t0 < run.t1]
    slow = max(win, key=lambda s: s.t1 - s.t0, default=None)
    if slow is not None:
        out(f"longest step: {slow.t1 - slow.t0:.3f} s at "
            f"+{slow.t0 - run.t0:.1f} s of the window")
    out(f"window: {run.counters1['steps'] - run.counters0['steps']} steps, "
        f"{len(due)} requests due, preemptions "
        f"{run.counters1['preemptions'] - run.counters0['preemptions']}, "
        f"compiles {counter.n}, trace_counts {dict(eng.trace_counts)}")

    if trace:
        metrics = {}
        for m in bench["per_layer"]:
            if "workloads" in m and wl["name"] not in m["workloads"]:
                continue
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if "workloads" not in m or wl["name"] in m["workloads"]}
    # free the program's state before the reference runs
    model.free_state(eng)
    picked = correct.sample(run, seed)
    del eng, prep.eng
    gc.collect()

    fn = family.reference(conf)
    readings = correct.compare(fn, params, picked,
                               correct.padded(traffic.max_context(mix)))
    verdict = correct.verdict(readings, limits)
    out(f"reference: {readings}")

    out(f"end to end: {e2e}")
    out(f"notes: {ctx.notes}")
    dev = devices[0]
    result = {"correct": verdict["correct"], "attempted": len(due),
              "failed": failed, "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices), "memory_peak_bytes": peak}}
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in reduced["gaps"][:10]]}
        out(f"idle by host span: {reduced['idle_by_span']}")
        out(f"kernels: {reduced['kernel_calls']} events, "
            f"{reduced['kernel_s']} s: {reduced['kernels']}")
        out(f"collectives: {reduced['collective_s']} s a chip; " + str({
            k[:48]: v for k, v in reduced["ops"].items()
            if trace_reduce.COLLECTIVE.match(k)}))
    result["checks"] = verdict["checks"]
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench, wl, conf, mix = load_cell(args.workload)
    except ValueError as e:
        log(f"bench/run.py: {e}")
        return 2
    from bench import correct, peaks as peaks_mod
    lim = correct.limits(wl["name"])
    import jax
    log(f"compile cache: {compile_cache()}")
    try:
        devices = chips(int(wl["chips"]))
    except NoChip as e:
        log(f"bench/run.py: {e}")
        return 2
    pk = peaks_mod.peaks(devices[0].device_kind)
    log(f"device: {devices[0].device_kind} x{len(devices)}, jax {jax.__version__}")
    result = run_cell(bench, wl, conf, mix, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, peaks=pk, limits=lim,
                      t_start=T_START)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']}, must be "
            f"{c['holds']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
