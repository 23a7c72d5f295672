"""TP collectives: the device time in which an exchange between chips ran
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all,
whole or as their start/done halves; bench/trace_reduce.py), as a share
of busy device time, both the mean over the chips. Reads 0 when no
collective ran."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["busy_s"]:
        return None
    return 100.0 * ctx.trace["collective_s"] / ctx.trace["busy_s"]
