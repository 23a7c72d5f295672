"""Open loop, Poisson arrivals: exponential gaps at ``rate_rps``."""
import numpy as np

from bench.arrivals import OpenLoop, grid


def make(params, period_s, horizon_s, phase):
    n = max(int(round(float(params["rate_rps"]) * period_s)), 1)
    return OpenLoop(grid(lambda u: -np.log1p(-u), n, 0, phase), period_s,
                    horizon_s)
