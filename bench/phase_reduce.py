"""Split the device idle time of a JAX profiler trace (``.xplane.pb``) by
the engine phase the host was in, and time each executable.

    python bench/phase_reduce.py <trace dir>

The engine's phases are the host spans named ``engine.*``
(serving/telemetry.py). Every device idle interval is cut at the
boundaries of the open host spans and each piece charged to the innermost
open engine span (a stage by its forward too, as ``engine.decode.wait``),
else to ``bench.wait`` (the load generator asleep, bench/driver.py) if that
is open, else to ``harness``. The pieces of a gap sum to the gap. The
window, the devices and the operations are those of bench/trace_reduce.py,
so ``idle_by_phase`` sums to its ``window_s - busy_s``. Executables are the
events on a device's ``XLA Modules`` line.
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.trace_reduce import (OPS_LINE, SPAN_PREFIX, Interval,  # noqa: E402
                                find, gaps_between, union)

Span = Tuple[float, float, str]

MODULES_LINE = "XLA Modules"
ENGINE_PREFIX = "engine."
WAIT = "bench.wait"         # the generator asleep: no request to serve
HARNESS = "harness"         # the host outside the engine and not asleep
STEP = "engine.step"
# the stages of each forward (chunk, decode, verify, prefill): an idle
# piece under one is named with its forward, as ``engine.decode.wait``
STAGES = ("engine.inputs", "engine.launch", "engine.wait", "engine.finish")


def label_segments(engine: Sequence[Span],
                   waits: Sequence[Span]) -> List[Span]:
    """Cut the time line at every boundary of the ``engine`` and ``waits``
    spans, and name each piece by the innermost engine span open over it
    (the one that opened last; ``engine`` spans nest, as one thread's
    do; a stage by its forward too, as ``engine.decode.wait``), else
    ``WAIT`` if a wait span is open, else ``HARNESS``. Returns the
    contiguous pieces from the first boundary to the last."""
    events = sorted({p for a, b, _ in (*engine, *waits) for p in (a, b)})
    opens: Dict[float, List[Tuple[int, Span]]] = defaultdict(list)
    for i, sp in enumerate(sorted(engine, key=lambda s: (s[0], -s[1]))):
        opens[sp[0]].append((i, sp))
    wait_opens: Dict[float, int] = defaultdict(int)
    wait_ends: Dict[float, int] = defaultdict(int)
    for a, b, _ in waits:
        wait_opens[a] += 1
        wait_ends[b] += 1
    active: Dict[int, Span] = {}
    n_wait = 0
    out: List[Span] = []
    for p, q in zip(events, events[1:]):
        for i in [i for i, sp in active.items() if sp[1] <= p]:
            del active[i]
        for i, sp in opens.get(p, ()):
            if sp[1] > p:
                active[i] = sp
        n_wait += wait_opens.get(p, 0) - wait_ends.get(p, 0)
        if active:
            inner = sorted(active)
            label = active[inner[-1]][2]
            if label in STAGES and len(inner) > 1:
                label = active[inner[-2]][2] + label[len("engine"):]
        else:
            label = WAIT if n_wait > 0 else HARNESS
        out.append((p, q, label))
    return out


def split_gap(gap: Interval, segments: Sequence[Span],
              starts: Sequence[float]) -> Iterable[Tuple[str, float]]:
    """(label, ns) pieces of ``gap`` along ``segments``; what no segment
    covers is ``HARNESS``. The pieces sum to the gap."""
    a, b = gap
    t = a
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    for s0, s1, label in segments[i:]:
        if s0 >= b:
            break
        lo, hi = max(s0, t), min(s1, b)
        if hi <= lo:
            continue
        if lo > t:
            yield HARNESS, lo - t
        yield label, hi - lo
        t = hi
    if t < b:
        yield HARNESS, b - t


def overlap(gap: Interval, merged: Sequence[Interval],
            starts: Sequence[float]) -> List[Interval]:
    """The parts of ``gap`` inside the merged intervals ``merged``."""
    a, b = gap
    out = []
    for s0, s1 in merged[max(bisect.bisect_right(starts, a) - 1, 0):]:
        if s0 >= b:
            break
        lo, hi = max(s0, a), min(s1, b)
        if hi > lo:
            out.append((lo, hi))
    return out


def module_name(name: str) -> str:
    """An executable's name without its program id:
    ``jit__chunk_step_impl(1167...)`` -> ``jit__chunk_step_impl``."""
    return re.sub(r"\(\d+\)$", "", name)


def longest_step(engine: Sequence[Span], lo: float,
                 hi: float) -> Optional[dict]:
    """The longest ``engine.step`` wholly inside [lo, hi], as a tree of
    ``{"name", "s", "at", "children"}`` (seconds; ``at`` from ``lo``)."""
    steps = [sp for sp in engine if sp[2] == STEP and sp[0] >= lo
             and sp[1] <= hi]
    if not steps:
        return None
    a, b, _ = max(steps, key=lambda sp: sp[1] - sp[0])
    root = {"name": STEP, "s": (b - a) * 1e-9, "at": (a - lo) * 1e-9,
            "children": []}
    stack = [(b, root)]
    inner = sorted((sp for sp in engine if a <= sp[0] and sp[1] <= b
                    and sp != (a, b, STEP)), key=lambda s: (s[0], -s[1]))
    for s0, s1, name in inner:
        while s0 >= stack[-1][0] and len(stack) > 1:
            stack.pop()
        node = {"name": name, "s": (s1 - s0) * 1e-9,
                "at": (s0 - lo) * 1e-9, "children": []}
        stack[-1][1]["children"].append(node)
        stack.append((s1, node))
    return root


def reduce_phases(busy: Dict[str, Sequence[Interval]],
                  waits: Sequence[Span], window: Interval,
                  engine: Sequence[Span],
                  modules: Dict[str, Sequence[Tuple[str, float, float]]],
                  ) -> dict:
    """``busy``: per device, its operations as (start_ns, end_ns);
    ``waits``: the generator's ``bench.wait`` spans; ``window``: the
    traced interval in ns; ``engine``: the engine's host spans;
    ``modules``: per device, its executables as (name, start_ns, end_ns).
    Returns seconds, averaged over devices: busy and window time as
    bench/trace_reduce.py reads them, the idle split by phase
    (``idle_by_phase``), the part of it that lies inside a running
    executable (``idle_in_modules``, by the same names: the device idle
    between the operations of one program), the engine steps wholly
    inside the window (``engine_steps``), device seconds per executable
    (``module_s``) and the longest engine step with its phases
    (``longest_step``)."""
    segments = label_segments(engine, waits)
    seg_starts = [s[0] for s in segments]
    lo, hi = window
    idle_by_phase: Dict[str, float] = defaultdict(float)
    idle_in_modules: Dict[str, float] = defaultdict(float)
    module_s: Dict[str, float] = defaultdict(float)
    n = max(len(busy), 1)
    busy_s = 0.0
    for dev, iv in sorted(busy.items()):
        clipped = []
        for name, a, b in modules.get(dev, ()):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                clipped.append((a, b))
                module_s[module_name(name)] += (b - a) * 1e-9 / n
        in_mod = union(clipped)
        mod_starts = [m[0] for m in in_mod]
        merged = union((max(a, lo), min(b, hi)) for a, b in iv
                       if min(b, hi) > max(a, lo))
        busy_s += sum(b - a for a, b in merged) * 1e-9 / n
        for g in gaps_between(merged, lo, hi):
            for label, ns in split_gap(g, segments, seg_starts):
                idle_by_phase[label] += ns * 1e-9 / n
            for h in overlap(g, in_mod, mod_starts):
                for label, ns in split_gap(h, segments, seg_starts):
                    idle_in_modules[label] += ns * 1e-9 / n
    return {"busy_s": busy_s, "window_s": (hi - lo) * 1e-9,
            "idle_by_phase": dict(idle_by_phase),
            "idle_in_modules": dict(idle_in_modules),
            "engine_steps": sum(1 for a, b, name in engine
                                if name == STEP and a >= lo and b <= hi),
            "module_s": dict(module_s),
            "longest_step": longest_step(engine, lo, hi)}


def read(path: str) -> dict:
    """Load ``path`` and reduce it over the span of every device operation
    and ``bench.*`` span in the trace (bench/trace_reduce.py's window)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    busy: Dict[str, List[Interval]] = {}
    modules: Dict[str, list] = {}
    waits: List[Span] = []
    engine: List[Span] = []
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_dev and line.name == MODULES_LINE:
                modules[plane.name] = [
                    (ev.name, float(ev.start_ns), float(ev.end_ns))
                    for ev in line.events]
                continue
            if is_dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                a, b = float(ev.start_ns), float(ev.end_ns)
                if is_dev:
                    busy.setdefault(plane.name, []).append((a, b))
                elif ev.name.startswith(ENGINE_PREFIX):
                    engine.append((a, b, ev.name))
                    continue
                elif ev.name.startswith(SPAN_PREFIX):
                    if ev.name == WAIT:
                        waits.append((a, b, ev.name))
                else:
                    continue
                lo, hi = min(lo, a), max(hi, b)
    return reduce_phases(busy, waits, (lo, hi), engine, modules)


def describe(span: dict) -> str:
    """``name ms (children)`` of a span tree from ``longest_step``."""
    text = f"{span['name'].removeprefix('engine.')} {1e3 * span['s']:.3f}"
    kids = ", ".join(describe(c) for c in span["children"])
    return f"{text} ({kids})" if kids else text


def report(r: dict) -> List[str]:
    """Lines for a reader: idle by phase and the longest engine step."""
    per = max(r["engine_steps"], 1)
    lines = [f"idle {1e3 * (r['window_s'] - r['busy_s']):.3f} ms of "
             f"{1e3 * r['window_s']:.3f} ms over {r['engine_steps']} "
             f"engine steps; ms per step by engine phase:"]
    for name, s in sorted(r["idle_by_phase"].items(), key=lambda kv: -kv[1]):
        inside = r["idle_in_modules"].get(name, 0.0)
        lines.append(f"  {name} {1e3 * s / per:.3f} (inside an executable "
                     f"{1e3 * inside / per:.3f})")
    lines.append("device ms per step by executable:")
    for name, s in sorted(r["module_s"].items(), key=lambda kv: -kv[1]):
        lines.append(f"  {name} {1e3 * s / per:.3f}")
    step = r["longest_step"]
    if step is not None:
        lines.append(f"longest engine step, ms, at +{step['at']:.2f} s of "
                     f"the trace: {describe(step)}")
    return lines


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    for line in report(read(find(argv[0]))):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
