"""Per-layer metric readers, one module per metric, found by the metric's
name in ``BENCHMARK.json``. Each defines ``read(ctx)`` and returns the
number in the metric's unit, or None when the run holds nothing to read;
the harness then leaves the metric out of the result.

``ctx`` (bench/run.py ``Context``) holds the driver's ``run``, the
cell's engine settings ``es``, the model's ``counts`` (its family's
``bench.flops.Counts``), the chip's ``peaks``, ``chips``, the ``trace``
reduction (or None; bench/trace_reduce.py, with each kernel's seconds by
its name), ``compiles`` counted in the window, ``peak_bytes`` and
``n_blocks``.
"""
import importlib


def load(name: str):
    return importlib.import_module(f"bench.layer_metrics.{name}")


def in_window(ctx):
    """Requests due inside the measured window."""
    run = ctx.run
    return [tr for tr in run.tracked if run.t0 <= tr.due < run.t1]


def traced_steps(ctx):
    """Engine steps that ran wholly inside the traced window."""
    if ctx.run.trace_span is None:
        return []
    a, b = ctx.run.trace_span
    return [s for s in ctx.run.steps if s.t0 >= a and s.t1 <= b]


def window_steps(ctx):
    run = ctx.run
    return [s for s in run.steps if s.t0 >= run.t0 and s.t1 <= run.t1]


def traced_flops(ctx) -> float:
    """Model operations of the tokens prefilled and decoded in the traced
    window: weights once per token, attention over each token's context,
    the head for decoded tokens and each prompt's last; prefill recomputed
    after a preemption does not count."""
    c, total = ctx.counts, 0.0
    for s in traced_steps(ctx):
        for length in s.decode_lens:
            total += c.token_flops(length)
        start, n, last = s.new_prefill
        if n:
            total += c.chunk_flops(start, n, last)
    return total
