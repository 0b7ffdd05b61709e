"""Shared by the hybrid_* readers: device seconds of the fused hybrid
kernel per sweep (``bench/kernels/hybrid.py``'s rule), and the host
seconds per sweep of the program's own spans, which a traced run of
``bench/drivers/hybrid_sessions.py`` hands over."""
from bench.kernels import hybrid


def kernel_s_per_sweep(ctx):
    summary, sweeps = ctx.get("trace"), ctx.get("sweeps")
    if summary is None or not sweeps:
        return None
    total = summary.op_seconds(hybrid.is_kernel)
    if total <= 0:
        return None
    return total / sweeps


def span_ms_per_sweep(ctx, name: str):
    spans, sweeps = ctx.get("hybrid_spans"), ctx.get("sweeps")
    if not spans or not sweeps or name not in spans:
        return None
    return spans[name] / sweeps * 1e3
