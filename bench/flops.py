"""Operations and bytes of the paged attention read and of a whole model
step, computed from shapes and the contexts actually served.

A model family (bench/families/) gives its model's :class:`Counts`: how
many layers attend through the paged pool, the widths of one such layer
(:class:`Attention`), the weights a token multiplies, and the recurrent
state a batch slot holds. Every count is the least the algorithm needs:
the paged read moves each live page of K and V once per sequence and
attention layer, and a step multiplies every weight once per token;
recomputed work does not count.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class Attention:
    """One attention layer as the paged read sees it."""
    heads: int
    kv_heads: int
    head_dim: int
    block: int
    kv_bytes: int = 2          # bytes per stored K or V element


def pages(length: int, block: int) -> int:
    return -(-int(length) // block)


def paged_read(a: Attention, length: int, rows: int) -> Tuple[float, float]:
    """One call of the paged read for one sequence and one layer: ``rows``
    query rows (1 in decode, the chunk's valid rows in prefill) against
    ``length`` cached positions. Returns (operations, bytes)."""
    if length <= 0:
        return 0.0, 0.0
    ops = 4.0 * rows * a.heads * a.head_dim * length       # q.k and p.v
    kv = 2.0 * pages(length, a.block) * a.block * a.kv_heads * a.head_dim \
        * a.kv_bytes
    # bf16 queries in, and the f32 partials (o, m, l) out
    io = rows * a.heads * (a.head_dim * 2 + (a.head_dim + 2) * 4)
    return ops, kv + io


@dataclasses.dataclass(frozen=True)
class Counts:
    """What a model's step costs, in the terms the per-layer readers use.

    ``attn_layers`` layers attend through the paged pool, each as
    ``attention``; a token multiplies ``matmul_params`` weights, the
    head's ``head_params`` of them included; a batch slot holds
    ``state_bytes`` of recurrent state beside its pages. A family whose
    layers do other work per token subclasses this and adds it to
    :meth:`token_flops` and :meth:`chunk_flops`."""
    attn_layers: int
    attention: Attention
    matmul_params: int
    head_params: int
    state_bytes: int = 0

    def paged_read(self, length: int, rows: int) -> Tuple[float, float]:
        """(operations, bytes) of one call for one sequence and one
        attention layer."""
        return paged_read(self.attention, length, rows)

    def _attend(self) -> float:
        return 4.0 * self.attn_layers * self.attention.heads \
            * self.attention.head_dim

    def token_flops(self, context: int) -> float:
        """Model operations of one decoded token at position ``context``
        (it attends to ``context`` earlier positions and itself), head
        included."""
        return 2.0 * self.matmul_params + self._attend() * (context + 1)

    def chunk_flops(self, start: int, n: int, last: bool) -> float:
        """A prefill chunk of ``n`` tokens at positions start..start+n-1;
        only the prompt's last token needs the head."""
        w = self.matmul_params - self.head_params
        attn = self._attend() * (n * start + n * (n + 1) / 2)
        return 2.0 * w * n + attn + (2.0 * self.head_params if last else 0.0)


def roofline_seconds(c: Counts, calls, flops_peak: float, bw_peak: float):
    """Least time of the calls on a chip of these peaks, each call bound by
    the larger of its operations over peak and its bytes over bandwidth,
    in every attention layer. Returns (seconds, seconds bound by compute,
    seconds bound by memory)."""
    total = by_ops = by_bytes = 0.0
    for length, rows in calls:
        o, b = c.paged_read(length, rows)
        t_o, t_b = o * c.attn_layers / flops_peak, b * c.attn_layers / bw_peak
        total += max(t_o, t_b)
        if t_o >= t_b:
            by_ops += t_o
        else:
            by_bytes += t_b
    return total, by_ops, by_bytes
