"""Operations and bytes of the paged attention read and of a whole model
step, computed from shapes and the contexts actually served.

``Shape`` holds the widths of a dense decoder (bench/model.py builds it from
a configuration file). Every count is the least the algorithm needs: the
paged read moves each live page of K and V once per sequence and layer, and
a step multiplies every weight once per token; recomputed work does not
count.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    block: int
    kv_bytes: int = 2          # bytes per stored K or V element


def pages(length: int, block: int) -> int:
    return -(-int(length) // block)


def paged_read(s: Shape, length: int, rows: int) -> Tuple[float, float]:
    """One call of the paged read for one sequence and one layer: ``rows``
    query rows (1 in decode, the chunk's valid rows in prefill) against
    ``length`` cached positions. Returns (operations, bytes)."""
    if length <= 0:
        return 0.0, 0.0
    ops = 4.0 * rows * s.heads * s.head_dim * length       # q.k and p.v
    kv = 2.0 * pages(length, s.block) * s.block * s.kv_heads * s.head_dim \
        * s.kv_bytes
    # bf16 queries in, and the f32 partials (o, m, l) out
    io = rows * s.heads * (s.head_dim * 2 + (s.head_dim + 2) * 4)
    return ops, kv + io


def roofline_seconds(s: Shape, calls, flops_peak: float, bw_peak: float):
    """Least time of the calls on a chip of these peaks, each call bound by
    the larger of its operations over peak and its bytes over bandwidth.
    Returns (seconds, seconds bound by compute, seconds bound by memory)."""
    total = by_ops = by_bytes = 0.0
    for length, rows in calls:
        o, b = paged_read(s, length, rows)
        t_o, t_b = o * s.layers / flops_peak, b * s.layers / bw_peak
        total += max(t_o, t_b)
        if t_o >= t_b:
            by_ops += t_o
        else:
            by_bytes += t_b
    return total, by_ops, by_bytes


def matmul_params(s: Shape) -> int:
    """Weights each token multiplies once per layer stack, plus the head."""
    attn = s.d_model * (s.heads + 2 * s.kv_heads) * s.head_dim \
        + s.heads * s.head_dim * s.d_model
    ffn = 3 * s.d_model * s.d_ff
    return s.layers * (attn + ffn) + s.d_model * s.vocab


def token_flops(s: Shape, context: int) -> float:
    """Model operations of one decoded token at position ``context`` (it
    attends to ``context`` earlier positions and itself), head included."""
    return 2.0 * matmul_params(s) + 4.0 * s.layers * s.heads * s.head_dim * (context + 1)


def chunk_flops(s: Shape, start: int, n: int, last: bool) -> float:
    """A prefill chunk of ``n`` tokens at positions start..start+n-1; only
    the prompt's last token needs the head."""
    w = matmul_params(s) - s.d_model * s.vocab
    attn = 4.0 * s.layers * s.heads * s.head_dim * (
        n * start + n * (n + 1) / 2)
    return 2.0 * w * n + attn + (2.0 * s.d_model * s.vocab if last else 0.0)
