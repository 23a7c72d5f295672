"""Kernel: the paged attention read's share of its roofline. The least
time of every call in the traced window (bench/flops.py: each decoded
row's live pages, each chunk's paged prefix, all layers; each call bound
by the larger of its operations over peak FLOP/s and its bytes over peak
bandwidth), over the summed device time of the kernel's events."""
from bench.flops import roofline_seconds
from bench.layer_metrics import traced_steps


def calls(steps):
    for s in steps:
        for length in s.decode_lens:
            yield length, 1
        if s.chunk is not None:
            yield s.chunk[0], s.chunk[1]


def read(ctx):
    if ctx.trace is None or not ctx.trace["kernel_s"]:
        return None
    least, by_ops, by_bytes = roofline_seconds(
        ctx.shape, calls(traced_steps(ctx)), ctx.peaks["bf16_flops"],
        ctx.peaks["hbm_bytes_per_s"])
    ctx.notes["paged_attn_bound"] = "memory" if by_bytes >= by_ops else "compute"
    return 100.0 * least / ctx.trace["kernel_s"]
