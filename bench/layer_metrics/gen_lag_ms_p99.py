"""Load generator: 99th percentile of how late a submission ran behind
its due time, over the requests due in the window (host clock)."""
import numpy as np

from bench.layer_metrics import in_window


def read(ctx):
    lags = [tr.submitted - tr.due for tr in in_window(ctx)]
    return 1e3 * float(np.percentile(lags, 99)) if lags else None
