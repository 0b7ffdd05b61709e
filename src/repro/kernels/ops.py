"""Kernel runners around the bloom-clock Pallas kernels.

Each runner pads its operands through one shared pad-and-crop plan
(``tile2d``), runs its Pallas call and crops the result back to the
true shape.  Runners decide nothing: the engine, the sharded strategy
and every block shape arrive as required keyword arguments, chosen by
``repro.causal.CausalEngine`` (the one dispatch layer).  Where
``tile2d`` clamps a block to the padded shape and the caller reports
it, the runner returns the block it used beside its result.

Every dispatch is counted in ``DISPATCHES`` by (op, engine, interpret),
so a run meant for the chip can prove that no kernel ran in the
interpreter.  The packed runners consume the quantized slab layout of
``kernels.pack`` (u8 window residuals plus a per-slot int32 base);
``repro.core.clock`` stays the algorithmic reference.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.hashing import bloom_indices
from repro.kernels.bloom_compare import bloom_merge_compare_pallas
from repro.kernels.bloom_matrix import (
    bloom_matrix_mxu_pallas,
    bloom_matrix_packed_pallas,
    bloom_matrix_pallas,
    bloom_matrix_tri_pallas,
    bloom_one_vs_many_packed_pallas,
    bloom_one_vs_many_pallas,
)
from repro.kernels.bloom_tick import bloom_tick_pallas
from repro.kernels.generate import bloom_hybrid_classify_pallas
from repro.kernels.ref import eq3_fp
from repro.kernels.template import resolve_interpret

__all__ = [
    "tick",
    "merge_compare",
    "classify_i32",
    "classify_packed",
    "classify_packed_sharded",
    "classify_hybrid",
    "compare_i32",
    "compare_packed",
    "compare_ring",
    "compare_replicated",
    "logical_bounds",
    "pad_to",
    "pick_block",
    "tile2d",
    "eq3_outer",
    "DISPATCHES",
    "MXU_SPAN_MAX",
]

LANE = 128  # TPU lane width

# Every kernel dispatch of this process, counted by (op, engine,
# interpret).  The jitted entry points (tick, merge_compare, the int32
# one-vs-many) count once per trace.  A run meant for the chip checks
# that no key ends in True.
DISPATCHES: collections.Counter = collections.Counter()

# widest value span (max - min logical cell) the MXU thermometer engine
# accepts; FLOPs scale linearly with it, so wide windows go elementwise
MXU_SPAN_MAX = 64
_MXU_SPAN_BUCKETS = (8, 16, 32, 64)


def _row_align(interpret: bool, lanes: bool = False) -> int:
    """Row padding grain of a slab entering a kernel.  On the chip a u8
    tile needs 32 rows, and rows that become an output's lane axis (the
    tri engine's square blocks, a rectangle's columns, the one-vs-many
    and hybrid dense result rows) need 128; the interpreter only needs
    the 8-row sublane grain."""
    if interpret:
        return 8
    return LANE if lanes else 32


def pad_to(x: jax.Array, mult: int, axis: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def pick_block(padded: int, want: int, lane: int = LANE) -> int:
    """Largest lane-multiple block <= want that divides ``padded``."""
    q = padded // lane
    best = 1
    for d in range(1, q + 1):
        if q % d == 0 and d * lane <= max(want, lane):
            best = d
    return best * lane


def tile2d(x: jax.Array, want_rows: int, want_lanes: int,
           *, row_align: int = 8, lane: int = LANE, pad_value=0):
    """Shared pad-and-crop plan for [R, C] slabs.

    Pads the lane axis to the TPU lane width and the row axis to the
    sublane alignment, then picks the largest aligned blocks <= the
    requested sizes that divide the padded shape.  Every runner goes
    through this instead of re-deriving padding; callers crop results
    back to the original ``x.shape``.

    Returns (x_padded, row_block, lane_block).
    """
    xp = pad_to(x, lane, axis=1, value=pad_value)
    bc = pick_block(xp.shape[1], want_lanes, lane=lane)
    xp = pad_to(xp, row_align, axis=0, value=pad_value)
    br = pick_block(xp.shape[0], want_rows, lane=row_align)
    return xp, br, bc


def _pad_base(base: jax.Array, n_rows: int) -> jax.Array:
    """Base lanes as the [Np, 1] int32 column the kernels expect."""
    b = jnp.asarray(base, jnp.int32).reshape(-1, 1)
    return pad_to(b, n_rows, axis=0)


def _row(x, dtype) -> jax.Array:
    """``x`` as a ``[1, n]`` row; a host array is reshaped before it
    moves, and a device array of that shape and dtype is kept."""
    if isinstance(x, np.ndarray):
        x = x.reshape(1, -1)
    return jnp.asarray(x, dtype).reshape(1, -1)


def _base_row(base: jax.Array, n_cols: int) -> jax.Array:
    """Base lanes as the [1, Np] int32 row the one-vs-many and hybrid
    kernels expect."""
    return pad_to(_row(base, jnp.int32), n_cols, axis=1)


def _flat_base(base) -> jax.Array:
    """Base as a 1-D int32 vector, keeping the caller's array object
    when it already is one (the replicated runner memoizes by it)."""
    if isinstance(base, jax.Array) and base.dtype == jnp.int32 \
            and base.ndim == 1:
        return base
    return jnp.asarray(base, jnp.int32).reshape(-1)


def _check_shards(mesh, axis: str, rows: int) -> None:
    d = mesh.shape[axis]
    if rows % d:
        raise ValueError(f"slab rows {rows} not divisible by {d} shards")


def _span_bucket(span: int) -> int:
    for b in _MXU_SPAN_BUCKETS:
        if span <= b:
            return b
    raise ValueError(f"value span {span} exceeds MXU_SPAN_MAX={MXU_SPAN_MAX}")


# ---------------------------------------------------------------------------
# tick / pairwise merge-compare
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "bb", "bm", "interpret"))
def tick(
    cells: jax.Array,        # [B, m] int32
    ev_hi: jax.Array,        # [B, E] uint32
    ev_lo: jax.Array,        # [B, E] uint32
    *,
    k: int = 4,
    bb: int = 8,
    bm: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched bloom tick: E events per clock, k probes each."""
    interpret = resolve_interpret(interpret)
    DISPATCHES[("tick", "pallas", interpret)] += 1
    B, m = cells.shape
    idx = bloom_indices(ev_hi, ev_lo, k, m)          # [B, E, k] uint32
    probes = idx.reshape(B, -1).astype(jnp.int32)    # [B, P], all < m
    cells_p, bb_eff, bm_eff = tile2d(cells, bb, bm)  # padded cols never hit
    probes_p = pad_to(probes, cells_p.shape[0], axis=0)  # pad rows: probe 0 hits
    out = bloom_tick_pallas(cells_p, probes_p, bb=bb_eff, bm=bm_eff,
                            interpret=interpret)
    return out[:B, :m]                               # padded-row incs sliced off


@functools.partial(jax.jit, static_argnames=("bb", "bm", "interpret"))
def merge_compare(
    a: jax.Array,            # [B, m] int32 logical cells
    b: jax.Array,
    *,
    bb: int = 8,
    bm: int = 512,
    interpret: bool | None = None,
):
    """Fused receive-path op. Returns dict with merged cells, dominance
    flags, sums and Eq.3 fp rates (see bloom_compare.py)."""
    interpret = resolve_interpret(interpret)
    DISPATCHES[("merge_compare", "pallas", interpret)] += 1
    B, m = a.shape
    # zero padding perturbs neither dominance (0<=0) nor sums; Eq. 3 must
    # use the TRUE m, passed statically to the kernel.
    a_p, bb_eff, bm_eff = tile2d(a, bb, bm)
    b_p, _, _ = tile2d(b, bb_eff, bm_eff)
    merged, flags, sums, fp = bloom_merge_compare_pallas(
        a_p, b_p, bb=bb_eff, bm=bm_eff, m_true=m, interpret=interpret
    )
    return {
        "merged": merged[:B, :m],
        "a_le_b": flags[:B, 0].astype(bool),
        "b_le_a": flags[:B, 1].astype(bool),
        "sum_a": sums[:B, 0],
        "sum_b": sums[:B, 1],
        "fp_a_before_b": fp[:B, 0],
        "fp_b_before_a": fp[:B, 1],
    }


# ---------------------------------------------------------------------------
# one-vs-many classify
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("bn", "bm", "interpret"))
def classify_i32(
    q: jax.Array,            # [m] int32 local (query) logical cells
    peers: jax.Array,        # [N, m] int32 peer slab logical cells
    *,
    bn: int,
    bm: int,
    interpret: bool,
):
    """One-vs-many fused classify on an int32 slab.

    Returns dict with per-peer ``q_le_p`` / ``p_le_q`` dominance flags,
    total sums and Eq. 3 fp rates both directions.  Zero padding
    perturbs neither dominance nor sums; Eq. 3 uses the TRUE m.
    """
    DISPATCHES[("one_vs_many", "i32", interpret)] += 1
    (m,) = q.shape
    N, mp_ = peers.shape
    assert m == mp_, (q.shape, peers.shape)
    peers_p, bn_eff, bm_eff = tile2d(
        peers, bn, bm, row_align=_row_align(interpret, lanes=True))
    q_p = pad_to(q[None, :], peers_p.shape[1], axis=1)
    flags, sums, fp = bloom_one_vs_many_pallas(
        q_p, peers_p, bn=bn_eff, bm=bm_eff, m_true=m, interpret=interpret
    )
    return _classify_dict(flags, sums, fp, N)


def _classify_dict(flags, sums, fp, N):
    """The result dict from the kernels' [2, Np] dense rows, cropped to
    the first N columns."""
    return {
        "q_le_p": flags[0, :N].astype(bool),
        "p_le_q": flags[1, :N].astype(bool),
        "sum_q": sums[0, 0],
        "sum_p": sums[1, :N],
        "fp_q_before_p": fp[0, :N],
        "fp_p_before_q": fp[1, :N],
    }


def _one_vs_many_body(q, peers, base, bn, bm, m: int, interpret: bool):
    """Pad one packed slab (or one row shard of it) and run the kernel;
    shared by the unsharded and shard_map'ed classify runners."""
    nd = peers.shape[0]
    peers_p, bn_eff, bm_eff = tile2d(
        peers, bn, bm, row_align=_row_align(interpret, lanes=True))
    q_p = pad_to(q[None, :], peers_p.shape[1], axis=1)
    base_p = _base_row(base, peers_p.shape[0])
    flags, sums, fp = bloom_one_vs_many_packed_pallas(
        q_p, peers_p, base_p, bn=bn_eff, bm=bm_eff, m_true=m,
        interpret=interpret)
    return flags[:, :nd], sums[:, :nd], fp[:, :nd]


def classify_packed(
    q: jax.Array,            # [m] int32 local (query) logical cells
    peers: jax.Array,        # [N, m] uint8 residual slab
    base: jax.Array,         # [N] (or [N, 1]) int32 per-slot offsets
    *,
    bn: int,
    bm: int,
    interpret: bool,
):
    """One-vs-many classify against a PACKED slab: u8 HBM reads, the
    per-row base is re-applied tile-locally in VMEM.  Same result dict
    as ``classify_i32``."""
    (m,) = q.shape
    N, mp_ = peers.shape
    assert m == mp_, (q.shape, peers.shape)
    DISPATCHES[("one_vs_many", "packed", interpret)] += 1
    flags, sums, fp = _one_vs_many_body(q, peers, base, bn, bm, m, interpret)
    return _classify_dict(flags, sums, fp, N)


def classify_packed_sharded(
    q: jax.Array,            # [m] int32 local (query) logical cells
    peers: jax.Array,        # [N, m] uint8 residual slab, row-sharded
    base: jax.Array,         # [N] (or [N, 1]) int32 per-slot offsets
    *,
    mesh,                    # jax.sharding.Mesh carrying ``axis``
    axis: str,               # mesh axis the slab rows are sharded over
    bn: int,
    bm: int,
    interpret: bool,
):
    """``classify_packed`` over a row-sharded slab via shard_map.

    The query is replicated; every device runs the packed one-vs-many
    Pallas kernel on its own ``[N/d, m]`` row shard — no cross-device
    traffic at all (the reduction is per-row).  Given the blocks the
    unsharded runner gets, every shard count tiles the m axis
    identically: the f32 sum accumulation order (and therefore the
    Eq. 3 fp bits) is bit-identical across shard counts.
    """
    (m,) = q.shape
    N, mp_ = peers.shape
    assert m == mp_, (q.shape, peers.shape)
    _check_shards(mesh, axis, N)
    DISPATCHES[("one_vs_many", "packed_sharded", interpret)] += 1
    fn = _sharded_classify_fn(mesh, axis, bn, bm, m, interpret)
    flags, sums, fp = fn(q, peers, jnp.asarray(base, jnp.int32).reshape(-1))
    return _classify_dict(flags, sums, fp, N)


@functools.lru_cache(maxsize=64)
def _sharded_classify_fn(mesh, axis: str, bn: int, bm: int, m: int,
                         interpret: bool):
    """Jitted shard_map'd one-vs-many classify, cached per (mesh, axis,
    blocks) so repeated gossip rounds reuse the compiled executable
    instead of re-wrapping and re-tracing the kernel every call."""
    def shard_body(qv, cu8, b):
        return _one_vs_many_body(qv, cu8, b, bn, bm, m, interpret)

    return jax.jit(jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P(axis, None), P(axis)),
        out_specs=(P(None, axis),) * 3,      # [2, N] dense rows
        check_vma=False,     # no replication rule for pallas_call
    ))


# ---------------------------------------------------------------------------
# hybrid classify (exact hot rows + packed tail, one fused kernel)
# ---------------------------------------------------------------------------

def classify_hybrid(
    q: jax.Array,            # [m] int32 local (query) logical cells
    v_local: int,            # local-chain version V the hot rows are vs
    hot_meta: jax.Array,     # [2, H] int32 rows: v, n_private per hot row
    hot_sums: jax.Array,     # [H] (or [H, 1], [1, H]) f32 shadow sums
    tail: jax.Array,         # [T, m] uint8 residual slab
    tail_base: jax.Array,    # [T] (or [T, 1]) int32 per-slot offsets
    *,
    bn: int,
    bm: int,
    interpret: bool,
):
    """One query vs an exact hot set PLUS a packed bloom tail, fused.

    Hot rows never touch bloom cells: their verdicts are integer
    compares of (v, n_private) chain coordinates against ``v_local`` —
    measured AND claimed fp are identically zero.  Tail rows run the
    packed one-vs-many math unchanged, so their verdicts/sums/fp stay
    bit-identical to a flat packed slab classified with the same bm.
    Returns ``(result, (bn, bm))``: the ``_classify_dict`` layout over
    H+T rows, hot first, and the blocks the kernel ran with.
    """
    (m,) = q.shape
    H = hot_meta.shape[1]
    T, mt_ = tail.shape
    assert m == mt_, (q.shape, tail.shape)
    assert H > 0 and T > 0, "hybrid needs both a hot set and a tail " \
        "(route single-representation slabs through the plain engines)"
    tail_p, bn_eff, bm_eff = tile2d(
        tail, bn, bm, row_align=_row_align(interpret, lanes=True))
    q_p = pad_to(q[None, :], tail_p.shape[1], axis=1)
    base_p = _base_row(tail_base, tail_p.shape[0])
    # pad hot columns to the tile grain with (v=0, n_private=0) filler —
    # cropped below, never observable
    meta_p = pad_to(jnp.asarray(hot_meta, jnp.int32), bn_eff, axis=1)
    hsum_p = pad_to(_row(hot_sums, jnp.float32), bn_eff, axis=1)
    vloc = jnp.full((1, 1), v_local, jnp.int32)
    DISPATCHES[("hybrid", "fused_hot_tail", interpret)] += 1
    out = bloom_hybrid_classify_pallas(
        q_p, vloc, meta_p, hsum_p, tail_p, base_p,
        bn=bn_eff, bm=bm_eff, m_true=m, interpret=interpret)
    Hp = meta_p.shape[1]
    if Hp != H:
        out = [jnp.concatenate([x[:, :H], x[:, Hp:]], axis=1) for x in out]
    return _classify_dict(*out, H + T), (bn_eff, bm_eff)


# ---------------------------------------------------------------------------
# all-pairs compare
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m_true",))
def eq3_outer(row_sums, col_sums, m_true: int):
    """Eq. 3 fp of "row happened-before col" as an outer product — the
    reference expression every engine finalizes with, and the one the
    engine re-finalizes promoted-row assemblies through, so their fp
    stays bit-identical."""
    return eq3_fp(row_sums[:, None], col_sums[None, :], m_true)


@functools.partial(jax.jit, static_argnames=("m_true",))
def _packed_row_sums(cells_u8, base, m_true: int):
    s = jnp.sum(cells_u8.astype(jnp.int32), axis=1).astype(jnp.float32)
    return s + jnp.asarray(base, jnp.int32).reshape(-1).astype(jnp.float32) \
        * m_true


def _matrix_dict(le, ge, row_sums, col_sums, m_true):
    return {
        "a_le_b": le,
        "b_le_a": ge,
        "concurrent": jnp.logical_not(jnp.logical_or(le, ge)),
        "fp": eq3_outer(row_sums, col_sums, m_true),
        "row_sums": row_sums,
        "col_sums": col_sums,
    }


def compare_packed(
    cells: jax.Array,           # [N, m] uint8 residual slab (rows)
    base: jax.Array,            # [N] (or [N, 1]) int32 per-slot offsets
    cols: jax.Array = None,     # [M, m] uint8 column slab; None -> symmetric
    col_base: jax.Array = None,
    *,
    engine: str,                # "tri" (symmetric only) | "full" | "mxu"
    bi: int,
    bj: int,
    bm: int,
    uniform_base: bool,
    interpret: bool,
    bounds: tuple[int, int] | None = None,   # mxu: (lo, span)
):
    """Tiled all-pairs compare over packed u8 slab(s).

    Symmetric calls (``cols is None``) sweep only the block-upper
    triangle under ``tri`` and mirror the rest by transposition.  The
    MXU thermometer needs the slabs' logical ``bounds`` (see
    ``logical_bounds``).  Returns dict with [N, M] ``a_le_b`` /
    ``b_le_a`` / ``concurrent`` flag matrices, the Eq. 3 ``fp`` of
    "row before col", and the per-row / per-col sums.
    """
    symmetric = cols is None
    if symmetric:
        cols, col_base = cells, base
    elif engine == "tri":
        raise ValueError("the tri engine needs a symmetric compare")
    N, m = cells.shape
    M = cols.shape[0]
    DISPATCHES[("matrix", engine, interpret)] += 1

    row_sums = _packed_row_sums(cells, base, m)
    col_sums = row_sums if symmetric else _packed_row_sums(cols, col_base, m)

    if engine == "tri":
        le, ge = _tri_flags(cells, base, max(bi, bj), bm, m,
                            not uniform_base, interpret)
        return _matrix_dict(le.astype(bool), ge.astype(bool),
                            row_sums, row_sums, m)

    if engine == "full":
        le, ge = _full_rect_flags(cells, base, cols, col_base, bi, bj, bm,
                                  m, not uniform_base, interpret)
        return _matrix_dict(le.astype(bool), ge.astype(bool),
                            row_sums, col_sums, m)

    if engine == "mxu":
        lo, span = bounds
        rows_p, cols_p, bi_eff, bj_eff, bm_eff = _rect_tiles(
            cells, cols, bi, bj, bm, interpret)
        viol = bloom_matrix_mxu_pallas(
            rows_p, cols_p, _pad_base(base, rows_p.shape[0]),
            _pad_base(col_base, cols_p.shape[0]),
            n_thresholds=_span_bucket(span), lo=lo,
            bi=bi_eff, bj=bj_eff, bm=bm_eff, m_true=m, interpret=interpret)
        return _mxu_finalize(viol, cells, base, cols, col_base,
                             row_sums, col_sums, N, M, m, lo)

    raise ValueError(f"unknown packed engine: {engine}")


def _full_rect_flags(rows, row_base, cols, col_base, bi, bj, bm,
                     m: int, with_base: bool, interpret: bool):
    """Pad-and-call for the packed full-rect engine, shared by the
    unsharded "full" branch and every sharded ring step (duplicate pads
    CSE away under jit).  Returns (le, ge) cropped to the true [N, M]."""
    N, M = rows.shape[0], cols.shape[0]
    rows_p, cols_p, bi_eff, bj_eff, bm_eff = _rect_tiles(
        rows, cols, bi, bj, bm, interpret)
    le, ge = bloom_matrix_packed_pallas(
        rows_p, cols_p, _pad_base(row_base, rows_p.shape[0]),
        _pad_base(col_base, cols_p.shape[0]),
        bi=bi_eff, bj=bj_eff, bm=bm_eff, m_true=m,
        with_base=with_base, interpret=interpret)
    return le[:N, :M], ge[:N, :M]


def _rect_tiles(rows, cols, bi, bj, bm, interpret: bool):
    """Pad a (rows, cols) slab pair for a rectangle engine: the columns
    land on the output's lane axis.  Returns (rows_p, cols_p, bi, bj,
    bm) with effective blocks."""
    rows_p, bi_eff, bm_eff = tile2d(rows, bi, bm,
                                    row_align=_row_align(interpret))
    cols_p, bj_eff, _ = tile2d(cols, bj, bm_eff,
                               row_align=_row_align(interpret, lanes=True))
    cols_p = pad_to(cols_p, rows_p.shape[1], axis=1)
    return rows_p, cols_p, bi_eff, bj_eff, bm_eff


def compare_replicated(
    cells: jax.Array,           # [N, m] uint8 residual slab, row-sharded
    base: jax.Array,            # [N] (or [N, 1]) int32 per-slot offsets
    *,
    mesh,                       # jax.sharding.Mesh carrying ``axis``
    axis: str,                  # mesh axis the slab rows are sharded over
    engine: str,                # the single-device engine to run
    bi: int,
    bj: int,
    bm: int,
    uniform_base: bool,
    interpret: bool,
    mesh_outputs: bool,
    bounds: tuple[int, int] | None = None,
):
    """Symmetric all-pairs over a row-sharded slab by NOT sharding it:
    gather the packed slab (u8 residuals + int32 bases, the cheapest
    representation to ship) onto one mesh device and run the plain
    single-device ``compare_packed`` there.  No per-step collectives and
    no SPMD program; this wins where mesh devices are time-sliced onto
    the same host cores (forced-host CI meshes) and ring collectives buy
    no parallelism.  Bit-identical to the unsharded sweep: it runs the
    very same kernel.

    ``mesh_outputs`` re-places the results row-sharded over the mesh —
    the ring's placement contract, required whenever the caller combines
    them with other mesh-sharded arrays (dead-slot masks, promoted-row
    overlays)."""
    _check_shards(mesh, axis, cells.shape[0])
    base = _flat_base(base)
    dev = mesh.devices.flat[0]
    out = compare_packed(
        _gathered_replica(cells, dev), _gathered_replica(base, dev),
        engine=engine, bi=bi, bj=bj, bm=bm, uniform_base=uniform_base,
        interpret=interpret, bounds=bounds)
    if mesh_outputs:
        out = {k: jax.device_put(v, NamedSharding(
                   mesh, P(axis, None) if v.ndim == 2 else P(axis)))
               for k, v in out.items()}
    DISPATCHES[("matrix", f"replicated_{engine}", interpret)] += 1
    return out


# gather memo for the replicated runner: registries call all_pairs
# repeatedly on the SAME slab array, so the cross-device copy is paid
# once per slab, not per call.  Keyed on object identity and guarded by
# a strong reference to the keyed array itself — an id can't be reused
# while the cache still holds the object it identifies.
_REPLICA_CACHE: dict = {}


def _gathered_replica(cells, dev):
    key = (id(cells), dev)
    hit = _REPLICA_CACHE.get(key)
    if hit is not None and hit[0] is cells:
        return hit[1]
    if len(_REPLICA_CACHE) >= 8:
        _REPLICA_CACHE.clear()
    gathered = jax.device_put(cells, dev)
    _REPLICA_CACHE[key] = (cells, gathered)
    return gathered


def compare_ring(
    cells: jax.Array,           # [N, m] uint8 residual slab, row-sharded
    base: jax.Array,            # [N] (or [N, 1]) int32 per-slot offsets
    *,
    mesh,                       # jax.sharding.Mesh carrying ``axis``
    axis: str,                  # mesh axis the slab rows are sharded over
    bi: int,
    bj: int,
    bm: int,
    uniform_base: bool,
    interpret: bool,
):
    """Symmetric all-pairs over a row-sharded slab as a ppermute ring.

    Each of the ``d`` devices holds a ``[N/d, m]`` row shard and
    circulates a column shard around the mesh ring; every ring step
    compares its resident rows against the visiting columns, filling
    one ``[N/d, N/d]`` block of its ``[N/d, N]`` block-row (see
    ``_sharded_ring_fn`` for the halved, double-buffered sweep).
    Bit-identical to the unsharded sweep: flags are exact (mirroring
    moves bits, it never recomputes them), and the fp / sums finalize
    runs through the SAME ``eq3_outer`` / ``_packed_row_sums``
    expressions.
    """
    N, m = cells.shape
    _check_shards(mesh, axis, N)
    base = _flat_base(base)
    DISPATCHES[("matrix", "ring_full", interpret)] += 1
    fn = _sharded_ring_fn(mesh, axis, N, bi, bj, bm, m, not uniform_base,
                          interpret)
    le, ge = fn(cells, base)
    row_sums = _packed_row_sums(cells, base, m)
    return _matrix_dict(le.astype(bool), ge.astype(bool),
                        row_sums, row_sums, m)


@functools.partial(jax.jit, static_argnames=("bi", "bm", "m", "with_base",
                                             "interpret"))
def _tri_flags(cells, b, bi, bm, m: int, with_base: bool, interpret: bool):
    """Triangle-sweep flags for one symmetric slab, mirrored onto the
    lower triangle (``le(i, j) == ge(j, i)``) and cropped — the
    single-device tri engine and the ring's per-device diagonal step,
    at half the pairwise work of a full rectangle."""
    n = cells.shape[0]
    cells_p, bi_eff, bm_eff = tile2d(
        cells, bi, bm, row_align=_row_align(interpret, lanes=True))
    le, ge = bloom_matrix_tri_pallas(
        cells_p, _pad_base(b, cells_p.shape[0]), bi=bi_eff, bm=bm_eff,
        m_true=m, with_base=with_base, interpret=interpret)
    k = le.shape[0] // bi_eff
    blk = jnp.arange(k).repeat(bi_eff)
    upper = blk[:, None] <= blk[None, :]
    return (jnp.where(upper, le, ge.T)[:n, :n],
            jnp.where(upper, ge, le.T)[:n, :n])


@functools.lru_cache(maxsize=64)
def _sharded_ring_fn(mesh, axis: str, N: int, bi: int, bj: int, bm: int,
                     m: int, with_base: bool, interpret: bool):
    """Jitted shard_map'd block-row ring, cached per (mesh, axis, shape,
    blocks) so the unrolled ppermute body traces once, not on every
    all_pairs call.

    Halved sweep: the matrix is symmetric under transposition-with-swap
    (``le(j, i) == ge(i, j)^T``), so only visiting offsets
    ``s = 0 .. d//2`` run the kernel.  For ``1 <= s <= (d-1)//2`` the
    device that computed block ``(i, i+s)`` ships both flag blocks
    transposed ``s`` hops forward, where they land exactly on the owner
    of the mirror block ``(i+s, i)``.

    Three refinements on top:

    - **Double buffering**: the column-shard ppermute feeding step
      ``s + 1`` is issued as soon as step ``s``'s shard arrives, BEFORE
      step ``s``'s kernel runs, so its only data dependence is the
      previous permute.  XLA's async collective-permute then overlaps
      the transfer with the compute under it.
    - **Triangle diagonal**: step 0 compares the resident shard with
      itself — a symmetric block — so it runs the tri engine over the
      block-upper half and mirrors locally, not a full rectangle.
    - **Half-way dedup** (even d): offset ``s = d/2`` pairs each device
      with its antipode, and BOTH used to compute the same mirrored
      work.  Now only devices ``i < d/2`` run the kernel; a partial
      ppermute ships the transposed flags to the antipode, and each
      side fills its block-column slot from whichever of
      (computed, received) is real on that device.

    Per-device kernel work is thus ``tri(N/d) + (d-1)/2 x rect(N/d)``
    — exactly ``tri(N) / d``: the sharded sweep does NO redundant
    compute at any shard count, it only adds the ring transfers.  The
    base vector is only circulated when bases are non-uniform (the
    kernels ignore it otherwise).
    """
    d = mesh.shape[axis]
    steps = d // 2 + 1

    def ring(cu8, b):
        nd = cu8.shape[0]
        my = jax.lax.axis_index(axis)
        le_acc = jnp.zeros((nd, N), jnp.int8)
        ge_acc = jnp.zeros((nd, N), jnp.int8)
        shift = [(i, (i - 1) % d) for i in range(d)]

        def permute(cols, cb):
            return (jax.lax.ppermute(cols, axis, shift),
                    jax.lax.ppermute(cb, axis, shift) if with_base else cb)

        cols, cb = cu8, b
        nxt = permute(cols, cb) if steps > 1 else None
        for s in range(steps):
            if s:
                cols, cb = nxt
                # issue the NEXT shard's permute before this step's
                # compute: the transfer overlaps the kernel below
                nxt = permute(cols, cb) if s + 1 < steps else None
            src = (my + s) % d          # column block visiting this step
            if s == 0:
                le, ge = _tri_flags(cu8, b, max(bi, bj), bm,
                                    m, with_base, interpret)
            elif d % 2 == 0 and s == d // 2:
                # half-way offset: my and my+d/2 hold each other's
                # mirror, so only the lower half computes; collectives
                # stay OUTSIDE the cond — every device executes them
                compute = my < d // 2
                zeros = (jnp.zeros((nd, nd), jnp.int8),) * 2
                le_c, ge_c = jax.lax.cond(
                    compute,
                    lambda: _full_rect_flags(cu8, b, cols, cb, bi, bj,
                                             bm, m, with_base, interpret),
                    lambda: zeros)
                half = [(i, i + d // 2) for i in range(d // 2)]
                le_r = jax.lax.ppermute(ge_c.T, axis, half)
                ge_r = jax.lax.ppermute(le_c.T, axis, half)
                le = jnp.where(compute, le_c, le_r)
                ge = jnp.where(compute, ge_c, ge_r)
            else:
                le, ge = _full_rect_flags(cu8, b, cols, cb, bi, bj, bm,
                                          m, with_base, interpret)
            le_acc = jax.lax.dynamic_update_slice(
                le_acc, le, (0, src * nd))
            ge_acc = jax.lax.dynamic_update_slice(
                ge_acc, ge, (0, src * nd))
            if 1 <= s <= (d - 1) // 2:
                # mirror block (my+s, my): ship the transposed flags s
                # hops forward; what arrives here came from my-s and is
                # this device's block (my, my-s)
                fwd = [(i, (i + s) % d) for i in range(d)]
                le_m = jax.lax.ppermute(ge.T, axis, fwd)
                ge_m = jax.lax.ppermute(le.T, axis, fwd)
                mirror = (my - s) % d
                le_acc = jax.lax.dynamic_update_slice(
                    le_acc, le_m, (0, mirror * nd))
                ge_acc = jax.lax.dynamic_update_slice(
                    ge_acc, ge_m, (0, mirror * nd))
        return le_acc, ge_acc

    return jax.jit(jax.shard_map(
        ring, mesh=mesh,
        in_specs=(P(axis, None), P(axis)),
        out_specs=(P(axis, None),) * 2,
        check_vma=False,     # no replication rule for pallas_call
    ))


def logical_bounds(cells, base, cols, col_base) -> tuple[int, int]:
    """Eager (host-synced) global (lo, span) of the logical values of
    one or two packed slabs — what the MXU thermometer is sized by."""
    b = jnp.asarray(base, jnp.int32).reshape(-1)
    cb = jnp.asarray(col_base, jnp.int32).reshape(-1)
    lo = int(jnp.minimum(b.min(), cb.min()))
    hi = int(jnp.maximum(
        (cells.astype(jnp.int32).max(axis=1) + b).max(),
        (cols.astype(jnp.int32).max(axis=1) + cb).max()))
    return lo, hi - lo


@functools.partial(jax.jit, static_argnames=("N", "M", "m_true", "lo"))
def _mxu_finalize(viol, cells, base, cols, col_base,
                  row_sums, col_sums, N, M, m_true, lo):
    # shifted sums stay < 2^24 so the f32 zero-tests below are exact;
    # the window shift cancels in the rank-1 identity
    sa = _packed_row_sums(cells, jnp.asarray(base).reshape(-1) - lo, m_true)
    sb = _packed_row_sums(cols, jnp.asarray(col_base).reshape(-1) - lo, m_true)
    v = viol[:N, :M]
    le = v == 0.0                                     # no violations a -> b
    ge = (v - sa[:, None] + sb[None, :]) == 0.0       # viol_ge via rank-1
    return _matrix_dict(le, ge, row_sums, col_sums, m_true)


def compare_i32(
    rows: jax.Array,         # [N, m] int32 logical cells
    cols: jax.Array,         # [M, m] int32 logical cells
    *,
    bi: int,
    bj: int,
    bm: int,
    interpret: bool,
):
    """Tiled all-pairs compare on int32 slabs: the broadcast reference
    ``repro.core.clock.comparability_matrix`` without the O(n^2 * m)
    materialization, for value spans wider than a byte.  Same result
    dict as ``compare_packed``."""
    N, m = rows.shape
    M, mc = cols.shape
    assert m == mc, (rows.shape, cols.shape)
    DISPATCHES[("matrix", "i32", interpret)] += 1
    col_sums = jnp.sum(cols, axis=1).astype(jnp.float32)           # [M]
    rows_p, cols_p, bi_eff, bj_eff, bm_eff = _rect_tiles(
        rows, cols, bi, bj, bm, interpret)
    col_sums_p = pad_to(col_sums[None, :], cols_p.shape[0], axis=1)
    le, ge, row_sums, fp = bloom_matrix_pallas(
        rows_p, cols_p, col_sums_p,
        bi=bi_eff, bj=bj_eff, bm=bm_eff, m_true=m, interpret=interpret,
    )
    le = le[:N, :M].astype(bool)
    ge = ge[:N, :M].astype(bool)
    return {
        "a_le_b": le,
        "b_le_a": ge,
        "concurrent": jnp.logical_not(jnp.logical_or(le, ge)),
        "fp": fp[:N, :M],
        "row_sums": row_sums[:N, 0],
        "col_sums": col_sums,
    }
