"""Shared machinery of the benchmark: loading by name, histories,
traffic, the profiler trace and the result line."""
