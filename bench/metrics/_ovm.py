"""Shared by the ovm_* readers: device seconds of the packed one-vs-many
kernel per sweep, matched by ``bench/kernels/ovm.py``'s rule."""
from bench.kernels import ovm


def kernel_s_per_sweep(ctx):
    summary, sweeps = ctx.get("trace"), ctx.get("sweeps")
    if summary is None or not sweeps:
        return None
    total = summary.op_seconds(ovm.is_kernel)
    if total <= 0:
        return None
    return total / sweeps
