"""Open loop, bursty arrivals: gamma gaps of mean 1 / ``rate_rps`` and
coefficient of variation ``cv`` (BurstGPT, arXiv:2401.17644)."""
from scipy.stats import gamma

from bench.arrivals import OpenLoop, grid


def make(params, period_s, horizon_s, phase):
    n = max(int(round(float(params["rate_rps"]) * period_s)), 1)
    shape = 1.0 / float(params["cv"]) ** 2
    return OpenLoop(grid(lambda u: gamma.ppf(u, shape), n, 0, phase),
                    period_s, horizon_s)
