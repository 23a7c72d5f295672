"""The one traffic generator: reads a mix from ``bench/traffic/<mix>.json``
and draws every request of a run from ``--seed``.

A mix gives the arrival process (``arrival.kind`` names a module under
``bench/arrivals/``), the prompt and output length distributions, and the
seconds of ramp before the window. The arrival process has a block of
requests (one window's arrivals, or one request per closed-loop client)
that repeats: the block's gaps and lengths sit on fixed grids of
quantiles in a fixed order, the seed draws where in that cycle the plan
starts, and request ``i`` takes entry ``i % block``. So every seed sends
the same requests in every stretch of a block; the prompt tokens
themselves, like the weights, are drawn from the seed per request.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np
from scipy.stats import norm

from bench import arrivals
from bench.arrivals import grid


def _ppf(spec):
    kind = spec["dist"]
    if kind == "lognormal":
        return lambda u: spec["median"] * np.exp(spec["sigma"] * norm.ppf(u))
    if kind == "fixed":
        return lambda u: np.full_like(u, float(spec["value"]))
    raise ValueError(f"unknown length distribution {kind!r}")


def lengths(spec, n: int, key: int, phase: float) -> np.ndarray:
    x = np.rint(grid(_ppf(spec), n, key, phase)).astype(np.int64)
    return np.clip(x, spec.get("min", 1), spec.get("max", np.iinfo(np.int32).max))


@dataclasses.dataclass
class Plan:
    arrivals: object            # arrivals.OpenLoop | arrivals.ClosedLoop
    prompt_lens: np.ndarray     # one block's sizes
    output_lens: np.ndarray
    seed: int
    vocab: int

    def prompt(self, i: int) -> List[int]:
        rng = np.random.default_rng([self.seed, i, 7])
        n = int(self.prompt_lens[i % len(self.prompt_lens)])
        return rng.integers(0, self.vocab, n).tolist()

    def output_len(self, i: int) -> int:
        return int(self.output_lens[i % len(self.output_lens)])


def plan(mix, seed: int, seconds: float, vocab: int) -> Plan:
    horizon = float(mix["ramp_s"]) + float(seconds)
    phase = float(np.random.default_rng([seed, 1]).random())
    arr = arrivals.load(mix["arrival"]["kind"]).make(
        mix["arrival"], float(seconds), horizon, phase)
    return Plan(arr, lengths(mix["prompt_len"], arr.block, 1, phase),
                lengths(mix["output_len"], arr.block, 2, phase), seed, vocab)


def max_context(mix) -> int:
    return int(mix["prompt_len"]["max"]) + int(mix["output_len"]["max"])
