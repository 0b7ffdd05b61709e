"""One reader per per-layer metric, named as in BENCHMARK.json; each
has ``read(ctx)`` and returns None where it finds nothing to read."""
