"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
time per device operation, kernel time, in all and by each Pallas
kernel's own name, collective time, and idle gaps named by the host span
(``bench.*``) that covers most of each gap. Every time is seconds of one
chip: the mean over the devices in the trace.

Device planes are those named ``/device:TPU:<n>``; their operations are
the events on the line named ``XLA Ops``. Host spans are the events named
``bench.*`` on any host plane. Both carry nanoseconds on one clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from array import array
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# the custom call that every Pallas kernel on a TPU is
PALLAS = "tpu_custom_call"
# the exchanges between chips, whole or as their async start/done halves.
# A TPU trace names an op by its HLO text ("%all-reduce.5 = bf16[...]
# all-reduce(...), ..."): the match is on the instruction's own name.
COLLECTIVE = re.compile(r"%?(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?"
                        r"(\.[\w.\-]*)?(\s|$)")


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


def merge(a: np.ndarray, b: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """``union`` on arrays: the starts and ends of the merged intervals."""
    if not len(a):
        return a, b
    o = np.argsort(a, kind="stable")
    a, b = a[o], b[o]
    reach = np.maximum.accumulate(b)
    head = np.ones(len(a), bool)
    head[1:] = a[1:] > reach[:-1]
    i = np.flatnonzero(head)
    return a[i], reach[np.append(i[1:] - 1, len(a) - 1)]


def kernel_name(op: str) -> str:
    """A kernel's own name (its ``pallas_call``'s ``name``) from its op's
    name: the HLO instruction's name (``%paged_decode.5 = ...``) less its
    ``%`` and its number."""
    return re.sub(r"\.\d+$", "", op.split(" = ", 1)[0].lstrip("%"))


def kernel_by_name(name: str, kernel: str) -> Optional[bool]:
    """Whether an op named ``name`` is the kernel, where the name decides:
    it names ``kernel``, or it is a whole HLO instruction (``%x = ...
    custom-call(...), custom_call_target=...``, as every TPU op is named),
    which names its target. None: the op's stats decide."""
    if not kernel:
        return False
    if kernel in name:
        return True
    return False if " = " in name else None


class Device:
    """One device's ops, reduced as they stream in (``add_line``): seconds per
    op name, kernel seconds and calls in all and by kernel name, the busy
    and collective intervals."""

    def __init__(self, kernel: str):
        self.kernel = kernel
        self.a, self.b = array("d"), array("d")
        self.ca, self.cb = array("d"), array("d")
        self.ops: Dict[str, float] = defaultdict(float)
        self.kernel_s, self.kernel_n = 0.0, 0
        self.kernels: Dict[str, List] = {}     # name: [seconds, calls]
        self._kind: Dict[str, Tuple[bool, Optional[bool], str]] = {}

    def add_line(self, events) -> None:
        """Every event of one ``XLA Ops`` line, read once: a name's
        collective match, kernel match and kernel name are worked out once
        per name, and
        an event's stats are read only where its name does not decide."""
        kernel, kind, ops = self.kernel, self._kind, self.ops
        add_a, add_b = self.a.append, self.b.append
        for ev in events:
            name, a, b = ev.name, ev.start_ns, ev.end_ns
            add_a(a)
            add_b(b)
            ops[name] += (b - a) * 1e-9
            k = kind.get(name)
            if k is None:
                k = kind[name] = (bool(COLLECTIVE.match(name)),
                                  kernel_by_name(name, kernel),
                                  kernel_name(name))
            if k[0]:
                self.ca.append(a)
                self.cb.append(b)
            hit = k[1]
            if hit is None:
                hit = any(kernel in str(v) for _, v in ev.stats)
            if hit:
                self.kernel_s += (b - a) * 1e-9
                self.kernel_n += 1
                per = self.kernels.setdefault(k[2], [0.0, 0])
                per[0] += (b - a) * 1e-9
                per[1] += 1


def name_gaps(ga: np.ndarray, gb: np.ndarray,
              spans: Sequence[Tuple[float, float, str]]) -> List[str]:
    """``name_gap`` of every gap [ga, gb), ``spans`` sorted. Where the host
    spans do not overlap one another, as the load generator's do not,
    only those from the one where a gap starts onward can overlap it, and
    a gap inside that one is named by it at once."""
    starts = [s[0] for s in spans]
    if not spans or any(s[1] > t for s, t in zip(spans, starts[1:])):
        return [name_gap(g, spans, starts) for g in zip(ga, gb)]
    s0, s1 = np.asarray(starts), np.asarray([s[1] for s in spans])
    first = np.maximum(np.searchsorted(s0, ga, side="right") - 1, 0)
    inside = (s0[first] <= ga) & (s1[first] >= gb)
    names = ["other"] * len(ga)
    for k in np.flatnonzero(inside):
        names[k] = spans[first[k]][2]
    for k in np.flatnonzero(~inside):
        a, b = ga[k], gb[k]
        best = 0.0
        for s0, e, n in spans[first[k]:]:
            if s0 >= b:
                break
            ov = min(b, e) - max(a, s0)
            if ov > best:
                best, names[k] = ov, n
    return names


def reduce_devices(devices: Dict[str, Device],
                   spans: List[Tuple[float, float, str]],
                   window: Interval, top: int = 10) -> dict:
    """``devices``: each device's ops, all inside ``window`` (ns);
    ``spans``: host (start_ns, end_ns, name). Returns seconds per chip
    (the mean over devices): busy, per-op, kernel time, the time in which
    a collective ran (``collective_s``); the kernels' calls over all
    devices; ``kernels``, each kernel's own seconds (``s``) and calls by
    its name; the ``top`` longest idle gaps named by host span, and the
    idle seconds under each host span."""
    spans = sorted(spans)
    lo, hi = window
    busy_s, ops, gaps = [], defaultdict(float), []
    kernel_s, kernel_n, collective_s = 0.0, 0, 0.0
    kernels: Dict[str, Dict] = {}
    idle_by_span = defaultdict(float)
    n = max(len(devices), 1)
    for _, d in sorted(devices.items()):
        s, e = merge(np.frombuffer(d.a), np.frombuffer(d.b))
        busy_s.append(float(np.sum(e - s)) * 1e-9)
        cs, ce = merge(np.frombuffer(d.ca), np.frombuffer(d.cb))
        collective_s += float(np.sum(ce - cs)) * 1e-9
        for k, v in d.ops.items():
            ops[k] += v
        kernel_s += d.kernel_s
        kernel_n += d.kernel_n
        for k, (sec, calls) in d.kernels.items():
            kk = kernels.setdefault(k, {"s": 0.0, "calls": 0})
            kk["s"] += sec
            kk["calls"] += calls
        ga, gb = np.append(lo, e), np.append(s, hi)
        keep = gb > ga
        ga, gb = ga[keep], gb[keep]
        dur = (gb - ga) * 1e-9
        names = name_gaps(ga, gb, spans)
        for name, sec in zip(names, dur.tolist()):
            idle_by_span[name] += sec / n
        for k in np.argsort(-dur, kind="stable")[:top]:
            gaps.append((names[k], float(dur[k])))
    return {"busy_s": sum(busy_s) / n, "window_s": (hi - lo) * 1e-9,
            "ops": {k: v / n for k, v in ops.items()},
            "kernel_s": kernel_s / n, "kernel_calls": kernel_n,
            "kernels": {k: {"s": v["s"] / n, "calls": v["calls"]}
                        for k, v in kernels.items()},
            "collective_s": collective_s / n,
            "gaps": sorted(gaps, key=lambda g: -g[1])[:top],
            "idle_by_span": dict(idle_by_span), "devices": len(busy_s)}


def read(path: str, kernel: str = PALLAS) -> dict:
    """Load ``path`` and reduce it over the span of every event in the
    trace, one device plane at a time. ``kernel`` marks the ops that are
    kernels, in an op's name or in its stats."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Device] = {}
    spans: List[Tuple[float, float, str]] = []
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = devices.setdefault(plane.name, Device(kernel))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                d.add_line(line.events)
            if len(d.a):
                lo = min(lo, min(d.a))
                hi = max(hi, max(d.b))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    a, b = ev.start_ns, ev.end_ns
                    spans.append((a, b, ev.name))
                    lo, hi = min(lo, a), max(hi, b)
    return reduce_devices(devices, spans, (lo, hi))
