"""Device time of the packed one-vs-many kernel per sweep, in ms
(device trace)."""
from bench.metrics._ovm import kernel_s_per_sweep


def read(ctx):
    s = kernel_s_per_sweep(ctx)
    return None if s is None else s * 1e3
