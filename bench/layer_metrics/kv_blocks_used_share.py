"""Paged cache: mean share of the pool's blocks owned by requests, over
the window's steps."""
from bench.layer_metrics import window_steps


def read(ctx):
    owned = [s.owned_blocks for s in window_steps(ctx)]
    return 100.0 * sum(owned) / (len(owned) * ctx.n_blocks) if owned else None
