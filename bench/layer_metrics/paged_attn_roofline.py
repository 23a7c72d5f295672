"""Kernel: the paged attention read's share of its roofline. The least
time of every call in the traced window (the family's counts,
bench/flops.py: each decoded row's live pages, each chunk's paged prefix,
every attention layer; each call bound by the larger of its operations
over peak FLOP/s and its bytes over peak bandwidth), over the summed
device time of the paged read's own kernels' events."""
from bench.flops import roofline_seconds
from bench.layer_metrics import traced_steps

# the Pallas kernels of the paged read (kernels/flash_decode.py), by name
KERNELS = ("paged_decode", "paged_prefix")


def calls(steps):
    for s in steps:
        for length in s.decode_lens:
            yield length, 1
        if s.chunk is not None:
            yield s.chunk[0], s.chunk[1]


def kernel_seconds(trace) -> float:
    """Device seconds of the paged read's kernels in a reduced trace."""
    return sum(trace["kernels"][k]["s"] for k in KERNELS
               if k in trace["kernels"])


def read(ctx):
    if ctx.trace is None:
        return None
    sec = kernel_seconds(ctx.trace)
    if not sec:
        return None
    least, by_ops, by_bytes = roofline_seconds(
        ctx.counts, calls(traced_steps(ctx)), ctx.peaks["bf16_flops"],
        ctx.peaks["hbm_bytes_per_s"])
    ctx.notes["paged_attn_bound"] = "memory" if by_bytes >= by_ops else "compute"
    return 100.0 * least / sec
