"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
time per device operation, kernel time, and idle gaps named by the host
span (``bench.*``) that covers most of each gap.

Device planes are those named ``/device:TPU:<n>``; their operations are
the events on the line named ``XLA Ops``. Host spans are the events named
``bench.*`` on any host plane. Both carry nanoseconds on one clock.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def gaps_between(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle stretches of [lo, hi] outside the merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def name_gap(gap: Interval, spans: Sequence[Tuple[float, float, str]],
             starts: Sequence[float]) -> str:
    """The host span overlapping ``gap`` the most (``other`` if none)."""
    a, b = gap
    best, name = 0.0, "other"
    i = max(bisect.bisect_right(starts, a) - 64, 0)
    for s0, s1, n in spans[i:]:
        if s0 >= b:
            break
        ov = min(b, s1) - max(a, s0)
        if ov > best:
            best, name = ov, n
    return name


def reduce_events(device: Dict[str, List[Tuple[str, float, float, dict]]],
                  spans: List[Tuple[float, float, str]],
                  window: Interval, kernel: str) -> dict:
    """``device``: per device, its ops as (name, start_ns, end_ns, stats);
    ``spans``: host (start_ns, end_ns, name); ``window``: the traced
    interval in ns. Returns seconds: busy (mean over devices), per-op,
    kernel time and calls, and the idle gaps named by host span."""
    spans = sorted(spans)
    starts = [s[0] for s in spans]
    lo, hi = window
    busy_s, ops, gap_list = [], defaultdict(float), []
    kernel_s, kernel_n = 0.0, 0
    for dev, events in sorted(device.items()):
        iv = []
        for name, a, b, stats in events:
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            iv.append((a, b))
            ops[name] += (b - a) * 1e-9
            if kernel and (kernel in name or any(
                    kernel in str(v) for v in stats.values())):
                kernel_s += (b - a) * 1e-9
                kernel_n += 1
        merged = union(iv)
        busy_s.append(covered(merged) * 1e-9)
        for g in gaps_between(merged, lo, hi):
            gap_list.append((name_gap(g, spans, starts), (g[1] - g[0]) * 1e-9))
    n = max(len(busy_s), 1)
    idle_by_span = defaultdict(float)
    for name, s in gap_list:
        idle_by_span[name] += s / n
    return {"busy_s": sum(busy_s) / n, "window_s": (hi - lo) * 1e-9,
            "ops": dict(ops), "kernel_s": kernel_s / n, "kernel_calls": kernel_n,
            "gaps": sorted(gap_list, key=lambda g: -g[1]),
            "idle_by_span": dict(idle_by_span), "devices": len(busy_s)}


def read(path: str, kernel: str) -> dict:
    """Load ``path`` and reduce it over the span of every event in the
    trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    spans: List[Tuple[float, float, str]] = []
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if is_dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                a, b = float(ev.start_ns), float(ev.end_ns)
                if is_dev:
                    device.setdefault(plane.name, []).append(
                        (ev.name, a, b, dict(ev.stats)))
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((a, b, ev.name))
                else:
                    continue
                lo, hi = min(lo, a), max(hi, b)
    return reduce_events(device, spans, (lo, hi), kernel)
