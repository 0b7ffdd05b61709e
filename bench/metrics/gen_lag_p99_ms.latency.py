"""99th percentile of how late the load generator sent each request
(host clock, the generator's own due-vs-sent stamps)."""
import numpy as np


def read(ctx):
    lag = ctx.get("gen_lag_s")
    if lag is None or len(lag) == 0:
        return None
    return float(np.percentile(lag, 99)) * 1e3
