"""Closed loop: ``clients`` callers, each waiting for its reply and then
``think_s`` seconds before it sends the next request."""
from bench.arrivals import ClosedLoop


def make(params, period_s, horizon_s, phase):
    return ClosedLoop(int(params["clients"]), float(params.get("think_s", 0)))
