"""Share of its roofline the packed one-vs-many kernel reaches, in
percent: the least time for its logical bytes at the chip's HBM
bandwidth (``bench/kernels/ovm.py``, ``bench/peaks.json``) over its
device time per sweep (device trace)."""
from bench.kernels import ovm
from bench.metrics._ovm import kernel_s_per_sweep


def read(ctx):
    s = kernel_s_per_sweep(ctx)
    if s is None or not ctx.get("peaks"):
        return None
    return 100.0 * ovm.least_seconds(ctx["rows"], ctx["m"], ctx["peaks"]) / s
