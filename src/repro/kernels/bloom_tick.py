"""Pallas TPU kernel: batched bloom-clock tick (scatter-free increment).

GPU formulation of a counting-bloom insert is k atomic scatter-adds per
event — hostile to TPU (no fast scatter; serialized DMA).  TPU-native
adaptation: the probe indices are precomputed on the VPU (cheap integer
mixing, see ``repro.core.hashing``) and the increment becomes a dense
one-hot accumulation per (batch, m)-tile:

    inc[b, c] = Σ_p  [probe[b, p] == c]

i.e. an iota-compare + reduction over the probe axis, fully vectorized,
with m padded to the 128-lane boundary.  Each m-tile sees the full probe
row, so the grid is embarrassingly parallel (no cross-tile accumulation,
no revisiting).

Block layout (VMEM per grid step, defaults bb=8, bm=512, P<=1024):
    cells tile   bb x bm   int32   16 KiB
    probe tile   bb x P    int32   32 KiB
    match cube   bb x P x bm bool  (register/VPU temporary, streamed)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["bloom_tick_kernel", "bloom_tick_pallas"]


def bloom_tick_kernel(probe_ref, cells_ref, out_ref, *, bm: int):
    """One (batch-tile, m-tile) grid step."""
    j = pl.program_id(1)
    probes = probe_ref[...]                      # [bb, P] int32 global cell ids
    cells = cells_ref[...]                       # [bb, bm]
    col0 = j * bm
    # local column ids of this m-tile, as a [1, bm] row for broadcasting
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1)
    # [bb, P, bm]: does probe p hit column c of this tile?
    match = probes[:, :, None] == cols[None, :, :]
    # accumulate in int32 regardless of cell dtype (16-bit cells would
    # otherwise reject the mixed-dtype store), cast back on the way out
    inc = jnp.sum(match.astype(jnp.int32), axis=1)  # [bb, bm]
    out_ref[...] = (cells.astype(jnp.int32) + inc).astype(cells.dtype)


@functools.partial(jax.jit, static_argnames=("bb", "bm", "interpret"))
def bloom_tick_pallas(
    cells: jax.Array,       # [B, m] int32 (m % bm == 0, B % bb == 0: caller pads)
    probes: jax.Array,      # [B, P] int32 global cell indices in [0, m)
    *,
    bb: int = 8,
    bm: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, m = cells.shape
    _, P = probes.shape
    assert m % bm == 0 and B % bb == 0, (B, m, bb, bm)
    grid = (B // bb, m // bm)
    return pl.pallas_call(
        functools.partial(bloom_tick_kernel, bm=bm),
        grid=grid,
        in_specs=[
            # every m-tile needs the full probe row of its batch tile
            pl.BlockSpec((bb, P), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, bm), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bb, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, m), cells.dtype),
        interpret=interpret,
        name="bloom_tick",
    )(probes, cells)
