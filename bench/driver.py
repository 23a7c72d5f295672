"""The load generator: drives ``Engine.submit`` / ``Engine.step`` from a
plan of arrivals (open or closed loop) and timestamps every token.

Each request is timed from when it fell due, so a stall delays every
request behind it. After each ``step()`` returns, the tokens each request
gained are stamped with that moment (``Request`` keeps no per-token times).
The same pass notes, from the requests themselves, what the step did: which
rows decoded at which context length, and which prompt chunk was paged,
so that the per-layer readers can count operations and bytes.

Host spans (``jax.profiler.TraceAnnotation``) mark what the loop is doing
(``bench.submit``, ``bench.step``, ``bench.record``, ``bench.wait``), so a
profiler trace can say what the host did in each device gap.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import time
from typing import Callable, List, Optional, Tuple

TERMINAL = ("finished", "timed_out", "cancelled", "rejected", "failed")


@dataclasses.dataclass
class Tracked:
    idx: int
    client: int
    req: object
    due: float
    submitted: float
    emits: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    seen: int = 0               # output tokens already stamped
    pre_seen: int = 0           # prompt tokens seen paged
    pre_hw: int = 0             # most prompt tokens ever paged (no recompute)
    finish: Optional[float] = None
    state: str = "waiting"


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decode_lens: List[int]              # KV length each decoded row read
    chunk: Optional[Tuple[int, int]]    # (start, n) of the paged chunk
    new_prefill: Tuple[int, int, bool]  # (start, n, last) not paged before
    owned_blocks: int


@dataclasses.dataclass
class Run:
    tracked: List[Tracked]
    steps: List[Step]
    t0: float                   # window start
    t1: float                   # window end
    counters0: dict             # engine counters at the window's start
    counters1: dict             # and at its end
    trace_span: Optional[Tuple[float, float]] = None


def counters(eng) -> dict:
    return {"steps": eng.steps,
            "traces": sum(eng.trace_counts.values()),
            "decode_tokens": eng.decode_tokens,
            "prefill_tokens": eng.prefill_tokens,
            "preemptions": eng.sched.n_preemptions}


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def drive(eng, plan, start: float, ramp_s: float, seconds: float, *,
          clock: Callable[[], float] = time.monotonic,
          sleep: Callable[[float], None] = time.sleep,
          spans: bool = True,
          trace: Optional[Tuple[float, Callable, Callable]] = None,
          on_window: Optional[Callable[[], None]] = None) -> Run:
    """Serve ``plan`` from ``start`` (a ``clock()`` reading): ``ramp_s``
    seconds of ramp, then a window of ``seconds``. ``trace`` is
    (seconds, start_fn, stop_fn): the profiler runs over the window's last
    seconds. ``on_window`` is called as the window opens. Returns when the window closes; requests still in flight are
    left where they are."""
    from repro.serving.engine import Rejected, Request

    span = _span if spans else (lambda name: contextlib.nullcontext())
    t0, t1 = start + ramp_s, start + ramp_s + seconds
    t_trace = t1 - trace[0] if trace else None
    heap = [(start + due, client, client) for due, client in
            plan.arrivals.initial()]
    heapq.heapify(heap)
    open_loop = not hasattr(plan.arrivals, "clients")
    next_idx = 0 if not open_loop else None
    live: List[Tracked] = []
    tracked: List[Tracked] = []
    steps: List[Step] = []
    c0 = None
    traced = None
    while True:
        now = clock()
        if c0 is None and now >= t0:
            c0 = counters(eng)
            if on_window is not None:
                on_window()
        if traced is None and t_trace is not None and now >= t_trace:
            trace[1]()
            traced = clock()
        if now >= t1:
            break
        if heap and heap[0][0] <= now:
            with span("bench.submit"):
                while heap and heap[0][0] <= now:
                    due, _, client = heapq.heappop(heap)
                    if open_loop:
                        idx = client
                    else:
                        idx, next_idx = next_idx, next_idx + 1
                    req = Request(rid=idx, tokens=plan.prompt(idx),
                                  max_new_tokens=plan.output_len(idx),
                                  arrival=due)
                    tr = Tracked(idx, client, req, due, clock())
                    tracked.append(tr)
                    try:
                        eng.submit(req)
                        live.append(tr)
                    except Rejected:
                        tr.state, tr.finish = "rejected", clock()
                        if not open_loop:
                            nxt = plan.arrivals.after_finish(client, tr.finish)
                            heapq.heappush(heap, (nxt, client, client))
            continue
        if not eng.sched.has_work:
            wake = min(heap[0][0] if heap else t1, t1,
                       t_trace if traced is None and t_trace else t1)
            with span("bench.wait"):
                sleep(max(wake - now, 0.0))
            continue
        with span("bench.step"):
            eng.step()
        t = clock()
        with span("bench.record"):
            decode_lens: List[int] = []
            chunk = None
            new_prefill = (0, 0, False)
            still: List[Tracked] = []
            for tr in live:
                r = tr.req
                pre = r.prefilled
                if pre != tr.pre_seen:
                    if pre > tr.pre_seen or pre > 0:
                        s0 = tr.pre_seen if pre > tr.pre_seen else 0
                        chunk = (s0, pre - s0)
                        if pre > tr.pre_hw:
                            first = max(s0, tr.pre_hw)
                            new_prefill = (first, pre - first,
                                           pre == len(r.tokens))
                            tr.pre_hw = pre
                    tr.pre_seen = pre
                out = len(r.output)
                if out > tr.seen:
                    tr.emits.append((t, out - tr.seen))
                    # a fresh request's first token comes from its prefill;
                    # every other token is one decoded row at this length
                    if out - tr.seen - (tr.seen == 0) > 0:
                        decode_lens.append(r.length - 2)
                    tr.seen = out
                if r.state in TERMINAL:
                    tr.state, tr.finish = r.state, t
                    if not open_loop:
                        nxt = plan.arrivals.after_finish(tr.client, t)
                        if nxt is not None:
                            heapq.heappush(heap, (nxt, tr.client, tr.client))
                else:
                    still.append(tr)
            live = still
            steps.append(Step(now, t, decode_lens, chunk, new_prefill,
                              eng.alloc.n_blocks - eng.alloc.n_available))
    if traced is not None:
        trace[2]()
        traced = (traced, clock())
    c1 = counters(eng)
    return Run(tracked, steps, t0, t1, c0 or counters(eng), c1, traced)
