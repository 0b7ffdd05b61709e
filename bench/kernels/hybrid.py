"""The fused hybrid classify kernel (``kernels/template.py``,
``_emit_hybrid``, device op ``bloom_hybrid_u8``): one query clock against
H exact hot rows and T packed tail rows in one grid.

Logical bytes count what the work needs, never the padded layout:

- read: T*m u8 residual cells and 4T for the int32 per-row base of the
  tail, 4m for the int32 query, and 12H for the hot rows' (v, n_private)
  int32 pair and float32 shadow sum;
- written: two one-byte flags and one float32 sum per row, hot and tail
  (Eq. 3's fp is written by the expression after the kernel, not by it).

Operations: per tail cell two compares and one add; a hot row costs
three compares.  They run on the vector unit, for which no peak is
published, so the roofline here is the byte bound.
"""
from __future__ import annotations


def bytes_moved(hot: int, tail: int, m: int) -> int:
    read = tail * m + 4 * tail + 4 * m + 12 * hot
    written = (2 + 4) * (hot + tail)
    return read + written


def ops(hot: int, tail: int, m: int) -> int:
    return 3 * tail * m + 3 * hot


def least_seconds(hot: int, tail: int, m: int, peaks: dict) -> float:
    """The least time the chip could take for one call."""
    return bytes_moved(hot, tail, m) / peaks["hbm_bytes_per_s"]


def is_kernel(op) -> bool:
    """Trace rule for the fused kernel's device events: the Pallas call
    is named ``bloom_hybrid_<pack>`` (``template.kernel_name``)."""
    return "bloom_hybrid" in op.name
