"""Pallas TPU kernel: fused bloom-clock merge + compare (+ Eq. 3 fp rate).

The runtime's receive path (§3 step 3) needs, per message:
    merged   = max(A, B)                  (the new clock)
    a_le_b   = all(A <= B)                (dominance -> ordering claim)
    b_le_a   = all(B <= A)
    ΣA, ΣB                                (Eq. 3 inputs)
    fp_ab, fp_ba                          (Eq. 3 both directions)

Done naively that is 5 separate HBM passes over the two cell arrays; all
of them are trivially byte-bound, so fusing them into ONE read of each
operand tile is a straight bandwidth win (~5x).  The m axis is tiled and
reduced with the revisited-output accumulation pattern: flags and sums
accumulate across m-tiles.  The fp rates are a per-row function of the
two sums, applied after the kernel by the reference expression
(``kernels.ref.eq3_fp``).

Grid: (B/bb, m/bm); the second axis revisits the per-batch outputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.template import (_accumulate, _eq3_pairs, _flag, _row_sum,
                                    _two_lanes)

__all__ = ["bloom_compare_kernel", "bloom_merge_compare_pallas"]


def bloom_compare_kernel(a_ref, b_ref, merged_ref, flags_ref, sums_ref):
    j = pl.program_id(1)
    a = a_ref[...]            # [bb, bm] int32
    b = b_ref[...]

    merged_ref[...] = jnp.maximum(a, b)
    # all(a <= b) as the min of a 0/1 int tile (direct compares, no wrap)
    flags = _two_lanes(
        jnp.min(_flag(a <= b, jnp.int32), axis=1, keepdims=True),
        jnp.min(_flag(a >= b, jnp.int32), axis=1, keepdims=True))
    _accumulate(j, flags_ref, sums_ref, flags,
                _two_lanes(_row_sum(a), _row_sum(b)))


@functools.partial(jax.jit, static_argnames=("bb", "bm", "m_true", "interpret"))
def bloom_merge_compare_pallas(
    a: jax.Array,   # [B, m] int32, padded: m % bm == 0, B % bb == 0
    b: jax.Array,
    *,
    bb: int = 8,
    bm: int = 512,
    m_true: int | None = None,   # Eq. 3 uses the un-padded cell count
    interpret: bool = False,
):
    B, m = a.shape
    assert a.shape == b.shape and m % bm == 0 and B % bb == 0
    merged, flags, sums = pl.pallas_call(
        bloom_compare_kernel,
        grid=(B // bb, m // bm),
        in_specs=[
            pl.BlockSpec((bb, bm), lambda i, j: (i, j)),
            pl.BlockSpec((bb, bm), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bm), lambda i, j: (i, j)),
            # per-batch reductions: revisited across j
            pl.BlockSpec((bb, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 2), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, m), a.dtype),
            jax.ShapeDtypeStruct((B, 2), jnp.int32),
            jax.ShapeDtypeStruct((B, 2), jnp.float32),
        ],
        interpret=interpret,
        name="bloom_merge_compare",
    )(a, b)
    # fp[:, 0] = P(A ⊆ B by chance), fp[:, 1] = P(B ⊆ A)
    return merged, flags, sums, _eq3_pairs(sums, m_true if m_true else m)
