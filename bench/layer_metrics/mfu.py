"""Model step: model operations of the traced window over the window's
seconds, the chips, and the chip's bf16 peak."""
from bench.layer_metrics import traced_flops


def read(ctx):
    if ctx.trace is None:
        return None
    f = traced_flops(ctx)
    return 100.0 * f / (ctx.trace["window_s"] * ctx.chips
                        * ctx.peaks["bf16_flops"]) if f else None
