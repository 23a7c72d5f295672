"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the window finished,
drawn from the seed and always holding the longest, goes through the
reference once each: prompt plus served tokens. At every served token the
gap by which its reference logit lies below the reference's best, in
standard deviations of that row's reference logits, is read; the widest gap
over the sample is compared with the cell's limit
(``bench/limits/<workload>.json``), which PERF.md derives from the readings
of sound runs and of the control.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench.reference import ROWS

LIMITS = Path(__file__).resolve().parent / "limits"
SAMPLE = 8


def limits(workload: str) -> Dict[str, float]:
    path = LIMITS / f"{workload}.json"
    if not path.exists():
        raise FileNotFoundError(f"no limits for {workload} ({path})")
    return json.loads(path.read_text())["limits"]


def sample(run, seed: int, k: int = SAMPLE) -> List:
    """Requests finished inside the window: the longest, and ``k - 1``
    more drawn from the seed."""
    done = [tr for tr in run.tracked if tr.state == "finished"
            and tr.finish is not None and run.t0 <= tr.finish <= run.t1]
    if not done:
        return []
    done.sort(key=lambda tr: tr.idx)
    longest = max(done, key=lambda tr: len(tr.req.tokens) + len(tr.req.output))
    rest = [tr for tr in done if tr is not longest]
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(len(rest), min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def padded(n: int) -> int:
    return -(-n // ROWS) * ROWS


def gaps(fn, weights, prompt: List[int], output: List[int], length: int,
         key: str = "next") -> np.ndarray:
    """Per served token: (reference max - reference logit of the token
    ``key`` names) / row sd, at the positions that predicted ``output``."""
    import jax.numpy as jnp
    seq = list(prompt) + list(output)
    toks = np.zeros((length,), np.int32)
    toks[: len(seq)] = seq
    out = {k: np.asarray(v) for k, v in fn(weights, jnp.asarray(toks)).items()}
    sl = slice(len(prompt) - 1, len(seq) - 1)
    return (out["max"][sl] - out[key][sl]) / out["sd"][sl]


def compare(fn, weights, picked: List, length: int) -> Dict[str, float]:
    worst, tokens = 0.0, 0
    finite = True
    for tr in picked:
        g = gaps(fn, weights, tr.req.tokens, tr.req.output, length)
        finite &= bool(np.isfinite(g).all())
        worst = max(worst, float(np.max(g)) if len(g) else 0.0)
        tokens += len(g)
    return {"worst_gap_sd": worst if finite else math.inf,
            "served_tokens": tokens, "requests": len(picked)}


def verdict(readings: Dict[str, float], lim: Dict[str, float]) -> Dict:
    """Every number compared beside its limit, and whether all hold."""
    checks = {"worst_gap_sd": {"value": readings["worst_gap_sd"],
                               "limit": lim["worst_gap_sd"], "holds": "<="},
              "sampled_requests": {"value": readings["requests"],
                                   "limit": 1, "holds": ">="}}
    ok = (readings["requests"] >= 1
          and readings["worst_gap_sd"] <= lim["worst_gap_sd"])
    return {"correct": bool(ok), "checks": checks}
