"""The packed one-vs-many classify kernel (``kernels/template.py``,
``_emit_one_vs_many`` with u8 packing): one query clock against N packed
peer rows.

Logical bytes count what the work needs, never the padded layout, so
the same work reads the same whatever implements it:

- read: N*m u8 residual cells, 4N for the int32 per-row base, 4m for
  the int32 query;
- written: two one-byte flags and one float32 sum per row.

Operations: per cell two compares and one add (both dominance
directions and the row sum).  They run on the vector unit, for which
no peak is published, so the roofline here is the byte bound.
"""
from __future__ import annotations


def bytes_moved(n: int, m: int) -> int:
    read = n * m + 4 * n + 4 * m
    written = 2 * n + 4 * n
    return read + written


def ops(n: int, m: int) -> int:
    return 3 * n * m


def least_seconds(n: int, m: int, peaks: dict) -> float:
    """The least time the chip could take for one call."""
    return bytes_moved(n, m) / peaks["hbm_bytes_per_s"]


def is_kernel(op) -> bool:
    """Trace rule for the packed kernel's device events, read off a
    v5e trace: the op is the ``one_vs_many_pallas`` custom call, and its
    peer operand is u8 (the int32 rim's call over promoted rows is the
    same kernel with an s32 operand)."""
    return "one_vs_many" in op.name and "u8[" in op.name
