"""Share of its roofline the fused hybrid kernel reaches, in percent:
the least time for its logical bytes at the chip's HBM bandwidth
(``bench/kernels/hybrid.py``, ``bench/peaks.json``) over its device time
per sweep (device trace)."""
from bench.kernels import hybrid
from bench.metrics._hybrid import kernel_s_per_sweep


def read(ctx):
    s = kernel_s_per_sweep(ctx)
    if s is None or not ctx.get("peaks"):
        return None
    return 100.0 * hybrid.least_seconds(ctx["hot"], ctx["tail"], ctx["m"],
                                        ctx["peaks"]) / s
