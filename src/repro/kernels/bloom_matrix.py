"""Bulk bloom-clock comparison engines (template-emitted; see below).

Since PR 7 every engine here is an INSTANCE of the parameterized
compare-kernel template (``kernels.template``), emitted by name in
``kernels.generate``; this module re-exports them under their historical
names so existing imports keep working.  The hand-rolled kernel bodies
that used to live here were deleted after each emitted instance was
pinned bit-identical (flags, Eq. 3 fp bits, per-row bases) against a
verbatim copy of the old code — the pins live in
``tests/test_template.py``.

What the engines compute (the design, shared by every instance):

``bloom_one_vs_many_pallas`` / ``bloom_one_vs_many_packed_pallas``
    grid (N/bn, m/bm); one query clock vs bn peers per step.  Dominance
    flags AND-accumulate and sums ADD-accumulate across m-tiles in VMEM
    scratch columns, stored once per row block as [2, N] dense rows
    (flags, sums); the Eq. 3 fp rates (both directions) are applied to
    the total sums after the kernel.  One HBM read of the peer slab
    total; the packed variant reads u8 residuals and widens in VMEM
    (+ a per-slot int32 base, read as a [1, N] row).

``bloom_matrix_pallas``
    grid (N/bi, M/bj, m/bm); tiled all-pairs int32 compare with in-kernel
    row sums (accumulated on the j == 0 stripe); Eq. 3 fp(row -> col) is
    the outer product of the row sums and the precomputed column sums,
    applied after the kernel.

``bloom_matrix_tri_pallas``
    symmetric all-pairs over ONE u8 slab.  ``ge(i, j) == le(j, i)``, so
    only the block-upper-triangle is swept (scalar-prefetched block index
    lists drive the grid) and each tile computes BOTH directions from a
    single int32 difference, swept in 8-row chunks: ``le = max(d) <= 0``,
    ``ge = min(d) >= 0``.  Half the pairs, one pairwise intermediate, u8
    HBM reads.

``bloom_matrix_packed_pallas``
    the same single-difference formulation on a full rectangle.

``bloom_matrix_mxu_pallas``
    MXU formulation: per-pair violation counts ``sum_m relu(a - b)`` as
    one ``dot_general`` per threshold via thermometer encoding; ``le`` iff the
    count is zero, opposite direction by the rank-1 identity with row/col
    sums.  Exact in f32 (counts <= m * T << 2^24); selected only for
    narrow value spans (the regime §4 promises).

Per-row bases (window offsets) are honored in all packed engines: folded
in as a clipped pair delta added to the reduced extremes (clipping at
±(U8_MAX + 1) cannot change a verdict since residual differences are
bounded by U8_MAX) or as a
per-row shift before encoding; padded lanes are masked in-kernel where
bases make zero-padding non-neutral.

These engines are also the per-shard building blocks of the mesh-sharded
registry paths (``ops.classify_packed_sharded`` / ``ops.compare_ring``).  Nothing in the kernel bodies is
placement-aware — flags are exact, so sharded results stay bit-identical
to the single-device sweeps.
"""
from __future__ import annotations

from repro.kernels.generate import (
    bloom_matrix_mxu_pallas,
    bloom_matrix_packed_pallas,
    bloom_matrix_pallas,
    bloom_matrix_tri_pallas,
    bloom_one_vs_many_packed_pallas,
    bloom_one_vs_many_pallas,
)

__all__ = [
    "bloom_one_vs_many_pallas",
    "bloom_one_vs_many_packed_pallas",
    "bloom_matrix_pallas",
    "bloom_matrix_tri_pallas",
    "bloom_matrix_packed_pallas",
    "bloom_matrix_mxu_pallas",
]
