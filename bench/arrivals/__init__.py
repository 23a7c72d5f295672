"""Arrival processes, one module per kind, found by the name a traffic file
gives under ``arrival.kind``.

Each module defines ``make(params, period_s, horizon_s, phase)`` and
returns an :class:`OpenLoop` or a :class:`ClosedLoop`. ``period_s`` is the
length of the measured window, ``horizon_s`` the seconds from the first
arrival to the window's end, and ``phase`` in [0, 1) the seed's rotation of
the fixed order (:func:`grid`). Either kind has a ``block``: request ``i``
takes the sizes of entry ``i % block`` of the plan (bench/traffic.py). The driver asks
``initial()`` for the first due times and ``after_finish()`` for the next
one of a client whose request has ended.
"""
from __future__ import annotations

import importlib
from typing import List, Optional, Tuple

import numpy as np


def load(kind: str):
    return importlib.import_module(f"bench.arrivals.{kind}")


def grid(ppf, n: int, key: int, phase: float) -> np.ndarray:
    """``n`` values at the quantiles (i + 0.5) / n of a distribution, in a
    fixed order (a permutation drawn once from ``key`` and ``n``), rotated
    by ``phase`` of the block. Every quantity of a plan has its own key, and
    all take the same rotation, so each request keeps its gap and sizes.

    Every seed thus sends the same requests in the same cyclic order and
    only starts the cycle elsewhere: on a repeating plan the window holds
    the same work. The order is not drawn per seed, because the tail of 66
    requests' times to first token swung by 40% between seeds with orders
    of their own, and by 1-2% between two runs of one seed (PERF.md)."""
    order = np.random.default_rng([key, n]).permutation(n)
    return np.roll(ppf((np.arange(n) + 0.5) / n)[order], int(phase * n))


class OpenLoop:
    """Requests fall due on a schedule, whatever the server does.

    One period of arrivals is drawn, its gaps scaled to span ``period_s``
    exactly, and repeated from time 0 to the horizon, each request keeping
    its sizes. A window as long as the period then holds exactly one copy of
    every request of the period, whatever the seed and wherever the window
    starts; once the ramp is longer than a request lives, the tokens emitted
    in the window are one period's worth, so the work does not depend on
    the seed's order."""

    def __init__(self, gaps: np.ndarray, period_s: float, horizon_s: float):
        gaps = np.asarray(gaps, np.float64) * (period_s / np.sum(gaps))
        offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        self.block = len(offsets)
        k = np.arange(int(np.ceil(horizon_s / period_s)))
        due = (k[:, None] * period_s + offsets[None, :]).ravel()
        self.due = due[due < horizon_s]

    def initial(self) -> List[Tuple[float, int]]:
        return [(float(t), i) for i, t in enumerate(self.due)]

    def after_finish(self, client: int, t: float) -> Optional[float]:
        return None


class ClosedLoop:
    """``clients`` callers, each sending its next request ``think_s`` after
    the previous one ends. Every ``clients`` requests in a row send the same
    sizes."""

    def __init__(self, clients: int, think_s: float):
        self.clients = self.block = clients
        self.think_s = think_s

    def initial(self) -> List[Tuple[float, int]]:
        return [(0.0, c) for c in range(self.clients)]

    def after_finish(self, client: int, t: float) -> Optional[float]:
        return t + self.think_s
