"""Host ms per sweep inside the program's ``hybrid.slab`` span: the
tail mirror's check (or rebuild) and the hot metadata's assembly
(program span)."""
from bench.metrics._hybrid import span_ms_per_sweep


def read(ctx):
    return span_ms_per_sweep(ctx, "hybrid.slab")
