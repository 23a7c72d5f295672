"""Scheduler: mean of ``Request.queue_time()`` (due to first admission)
over the requests due in the window that were admitted."""
from bench.layer_metrics import in_window


def read(ctx):
    waits = [tr.req.queue_time() for tr in in_window(ctx)
             if tr.req.queue_time() is not None]
    return 1e3 * sum(waits) / len(waits) if waits else None
