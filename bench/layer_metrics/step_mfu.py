"""Model step: model operations of the traced window over the seconds the
device was busy and the chip's bf16 peak: the whole step's share of peak
while it runs, which bounds any kernel's gain."""
from bench.layer_metrics import traced_flops


def read(ctx):
    if ctx.trace is None or not ctx.trace["busy_s"]:
        return None
    f = traced_flops(ctx)
    return 100.0 * f / (ctx.trace["busy_s"] * ctx.chips
                        * ctx.peaks["bf16_flops"]) if f else None
