"""End-to-end metrics of one measured window, from the driver's stamps.

* ``output_tok_s``: output tokens emitted inside the window over its
  seconds. Every token counts, whether its request finishes or not.
* ``ttft_p95_ms``: 95th percentile of time to first token over every
  request due inside the window, from its due time. A request with no token
  when the window closes counts at its age then, so a stall raises the tail.
* ``itl_p95_ms``: 95th percentile of every gap between consecutive tokens
  of a request whose later token falls in the window (tokens stamped after
  one step together have a gap of 0), and of the open gap, at the window's
  close, of each request still waiting for its next token. Not a tail of
  per-request means.
"""
from __future__ import annotations

from typing import Dict, Iterable, List

import numpy as np

P = 95


def _pct(values: List[float], q: float = P) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q)) if values \
        else float("nan")


def ttfts(tracked: Iterable, t0: float, t1: float) -> List[float]:
    out = []
    for tr in tracked:
        if not t0 <= tr.due < t1:
            continue
        first = tr.emits[0][0] if tr.emits else None
        out.append((first if first is not None and first <= t1 else t1)
                   - tr.due)
    return out


def gaps(tracked: Iterable, t0: float, t1: float) -> List[float]:
    out = []
    for tr in tracked:
        prev = None
        for t, n in tr.emits:
            if t > t1:
                break
            if prev is not None and t >= t0:
                out.append(t - prev)
                out.extend([0.0] * (n - 1))
            elif t >= t0:
                out.extend([0.0] * (n - 1))
            prev = t
        waiting = tr.finish is None or tr.finish > t1
        if prev is not None and waiting:
            out.append(t1 - prev)
    return out


def tokens_in(tracked: Iterable, t0: float, t1: float) -> int:
    return sum(n for tr in tracked for t, n in tr.emits if t0 <= t <= t1)


def end_to_end(tracked: List, t0: float, t1: float) -> Dict[str, float]:
    return {"output_tok_s": tokens_in(tracked, t0, t1) / (t1 - t0),
            "ttft_p95_ms": 1e3 * _pct(ttfts(tracked, t0, t1)),
            "itl_p95_ms": 1e3 * _pct(gaps(tracked, t0, t1))}
