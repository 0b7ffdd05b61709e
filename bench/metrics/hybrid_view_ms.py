"""Host ms per sweep inside the program's ``hybrid.view`` span: the
readback of the sweep's verdicts, sums and fp and the ``HybridView``
built from them (program span)."""
from bench.metrics._hybrid import span_ms_per_sweep


def read(ctx):
    return span_ms_per_sweep(ctx, "hybrid.view")
