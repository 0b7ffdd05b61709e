"""Device time of the fused hybrid kernel per sweep, in ms (device
trace)."""
from bench.metrics._hybrid import kernel_s_per_sweep


def read(ctx):
    s = kernel_s_per_sweep(ctx)
    return None if s is None else s * 1e3
