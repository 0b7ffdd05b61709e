"""Named engine instances emitted from the compare-kernel template.

This module is the ONLY place the seven production engines are defined:
each public function below builds a ``CompareSpec`` from its knobs and
calls ``template.emit`` — there are no hand-rolled kernel bodies left
anywhere in the tree.  Signatures are byte-for-byte the ones the old
``bloom_matrix`` wrappers exposed, and every instance is pinned
bit-identical to its pre-refactor kernel by ``tests/test_template.py``
(which carries verbatim copies of the deleted bodies as references).

``ENGINE_SPECS`` names the default spec behind each instance — the
autotuner sweeps neighborhoods of these points, and docs/tests introspect
it instead of reverse-engineering knob defaults from call sites.
"""
from __future__ import annotations

import jax

from repro.kernels.template import CompareSpec, emit

__all__ = [
    "ENGINE_SPECS",
    "bloom_one_vs_many_pallas",
    "bloom_one_vs_many_packed_pallas",
    "bloom_matrix_pallas",
    "bloom_matrix_tri_pallas",
    "bloom_matrix_packed_pallas",
    "bloom_matrix_mxu_pallas",
    "bloom_hybrid_classify_pallas",
]

# the template point each named engine is an instance of (default blocks)
ENGINE_SPECS = {
    "one_vs_many_i32": CompareSpec(
        topology="one_vs_many", pack="i32", bi=8, bm=512, with_stats=True),
    "one_vs_many_packed": CompareSpec(
        topology="one_vs_many", pack="u8", bi=8, bm=512,
        with_base=True, with_stats=True),
    "matrix_i32_stats": CompareSpec(
        topology="rect", pack="i32", bi=8, bj=128, bm=512, with_stats=True),
    "matrix_tri": CompareSpec(topology="tri", pack="u8", bi=128, bm=512),
    "matrix_rect": CompareSpec(
        topology="rect", pack="u8", bi=128, bj=128, bm=512),
    "matrix_mxu": CompareSpec(
        topology="mxu", pack="u8", bi=128, bj=128, bm=128,
        with_base=True, n_thresholds=64),
    "hybrid_one_vs_many": CompareSpec(
        topology="hybrid", pack="u8", bi=8, bm=512,
        with_base=True, with_stats=True),
}


def bloom_one_vs_many_pallas(
    q: jax.Array,        # [1, m] int32, padded: m % bm == 0
    peers: jax.Array,    # [N, m] int32, N % bn == 0
    *,
    bn: int = 8,
    bm: int = 512,
    m_true: int | None = None,
    interpret: bool = False,
):
    """One-vs-many classify (int32 peers): per-peer flags, sums, Eq. 3 fp,
    each a [2, N] pair of dense rows."""
    fn = emit(CompareSpec(topology="one_vs_many", pack="i32",
                          bi=bn, bm=bm, with_stats=True))
    return fn(q, peers, m_true=m_true, interpret=interpret)


def bloom_one_vs_many_packed_pallas(
    q: jax.Array,        # [1, m] int32 logical query, zero-padded
    peers: jax.Array,    # [N, m] uint8 residual slab, N % bn == 0
    base: jax.Array,     # [1, N] int32 per-slot offsets (a dense row)
    *,
    bn: int = 8,
    bm: int = 512,
    m_true: int | None = None,
    interpret: bool = False,
):
    """One-vs-many classify against a PACKED peer slab (u8 HBM reads)."""
    fn = emit(CompareSpec(topology="one_vs_many", pack="u8",
                          bi=bn, bm=bm, with_base=True, with_stats=True))
    return fn(q, peers, base, m_true=m_true, interpret=interpret)


def bloom_matrix_pallas(
    rows: jax.Array,       # [N, m] int32, padded: N % bi == 0, m % bm == 0
    cols: jax.Array,       # [M, m] int32, M % bj == 0
    col_sums: jax.Array,   # [1, M] float32 total increments per column clock
    *,
    bi: int = 8,
    bj: int = 128,
    bm: int = 512,
    m_true: int | None = None,
    interpret: bool = False,
):
    """Tiled all-pairs int32 compare with in-kernel sums + Eq. 3 fp."""
    fn = emit(CompareSpec(topology="rect", pack="i32",
                          bi=bi, bj=bj, bm=bm, with_stats=True))
    return fn(rows, cols, col_sums, m_true=m_true, interpret=interpret)


def bloom_matrix_tri_pallas(
    cells: jax.Array,      # [N, m] uint8 residuals, N % bi == 0, m % bm == 0
    base: jax.Array,       # [N, 1] int32 per-slot window offsets
    *,
    bi: int = 128,
    bm: int = 512,
    m_true: int | None = None,
    with_base: bool = False,
    interpret: bool = False,
):
    """Symmetric all-pairs compare over one packed slab (upper triangle)."""
    fn = emit(CompareSpec(topology="tri", pack="u8",
                          bi=bi, bj=bi, bm=bm, with_base=with_base))
    return fn(cells, base, m_true=m_true, interpret=interpret)


def bloom_matrix_packed_pallas(
    rows: jax.Array,       # [N, m] uint8, N % bi == 0, m % bm == 0
    cols: jax.Array,       # [M, m] uint8, M % bj == 0
    row_base: jax.Array,   # [N, 1] int32
    col_base: jax.Array,   # [M, 1] int32
    *,
    bi: int = 128,
    bj: int = 128,
    bm: int = 512,
    m_true: int | None = None,
    with_base: bool = False,
    interpret: bool = False,
):
    """Full-rectangle packed compare: (le, ge) int8 [N, M]."""
    fn = emit(CompareSpec(topology="rect", pack="u8",
                          bi=bi, bj=bj, bm=bm, with_base=with_base))
    return fn(rows, cols, row_base, col_base,
              m_true=m_true, interpret=interpret)


def bloom_matrix_mxu_pallas(
    rows: jax.Array,       # [N, m] uint8
    cols: jax.Array,       # [M, m] uint8
    row_base: jax.Array,   # [N, 1] int32
    col_base: jax.Array,   # [M, 1] int32
    *,
    n_thresholds: int,     # static value-span budget T (window width)
    lo: int,               # static minimum logical value across both slabs
    bi: int = 128,
    bj: int = 128,
    bm: int = 128,
    m_true: int | None = None,
    interpret: bool = False,
):
    """MXU dominance reduction: violation counts via one dot_general."""
    fn = emit(CompareSpec(topology="mxu", pack="u8",
                          bi=bi, bj=bj, bm=bm, with_base=True,
                          n_thresholds=n_thresholds))
    return fn(rows, cols, row_base, col_base,
              lo=lo, m_true=m_true, interpret=interpret)


def bloom_hybrid_classify_pallas(
    q: jax.Array,          # [1, m] int32 logical query, zero-padded
    v_local: jax.Array,    # [1, 1] int32 local-chain version V
    hot_meta: jax.Array,   # [2, H] int32 rows: v, n_private per hot row
    hot_sums: jax.Array,   # [1, H] float32 shadow-row total sums
    tail: jax.Array,       # [T, m] uint8 residual slab, T % bn == 0
    tail_base: jax.Array,  # [1, T] int32 per-slot offsets
    *,
    bn: int = 8,
    bm: int = 512,
    m_true: int | None = None,
    interpret: bool = False,
):
    """Fused hot+tail classify: exact verdicts (fp ≡ 0) for the hot
    rows, packed one-vs-many bloom verdicts for the tail, one kernel."""
    fn = emit(CompareSpec(topology="hybrid", pack="u8",
                          bi=bn, bm=bm, with_base=True, with_stats=True))
    return fn(q, v_local, hot_meta, hot_sums, tail, tail_base,
              m_true=m_true, interpret=interpret)
