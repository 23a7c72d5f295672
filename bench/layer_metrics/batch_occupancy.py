"""Scheduler: mean rows decoded per decode step over ``max_batch``, over
the window's steps that decoded."""
from bench.layer_metrics import window_steps


def read(ctx):
    rows = [len(s.decode_lens) for s in window_steps(ctx) if s.decode_lens]
    if not rows:
        return None
    return 100.0 * sum(rows) / (len(rows) * ctx.es["max_batch"])
