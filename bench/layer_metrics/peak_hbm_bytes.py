"""Device: ``memory_stats()["peak_bytes_in_use"]`` on the fullest chip,
read when the window closes."""


def read(ctx):
    return float(ctx.peak_bytes) if ctx.peak_bytes else None
