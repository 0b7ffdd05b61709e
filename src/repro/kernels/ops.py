"""Kernel wrappers around the bloom-clock Pallas kernels.

Handles: probe-index precomputation (hashing), the shared pad-and-crop
plan (``tile2d`` — every wrapper pads through it instead of duplicating
padding logic), platform dispatch (interpret=True off-TPU so the SAME
kernel bodies are exercised on CPU; every dispatch records which mode
it resolved to in ``DISPATCHES``), engine selection for the
comparison kernels (packed-u8 triangle / rectangle / MXU thermometer /
legacy int32 — consulted from the measured ``kernels.autotune`` table),
and un-padding.

The packed engines consume the quantized slab layout from
``kernels.pack`` (u8 window residuals + per-slot int32 base).  The
int32 entry points (``_compare_matrix`` / ``_classify_vs_many``) remain
drop-in: ``_compare_matrix`` packs on the fly whenever the value span
fits a byte and silently falls back to the int32 kernel otherwise.

PUBLIC SURFACE: the comparison wrappers here are the engine room of
``repro.causal.CausalEngine`` — new code should call its two verbs
(``engine.classify`` / ``engine.pairs``) instead of these.  The
pre-front-door names (``compare_matrix``, ``classify_vs_many``, ...)
remain importable as thin ``DeprecationWarning`` shims that delegate to
the same implementations, so their results are bit-identical.
``repro.core.clock`` stays the algorithmic reference.
"""
from __future__ import annotations

import collections
import functools
import math
import warnings

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.hashing import bloom_indices
from repro.kernels import autotune
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
from repro.kernels.pack import U8_MAX
from repro.kernels.ref import eq3_fp
from repro.kernels.template import resolve_interpret

__all__ = [
    "tick",
    "merge_compare",
    "classify_vs_many",
    "classify_vs_many_packed",
    "classify_vs_many_packed_sharded",
    "overlay_wide_classify",
    "compare_matrix",
    "compare_matrix_packed",
    "compare_matrix_packed_sharded",
    "pad_to",
    "pick_block",
    "tile2d",
    "eq3_outer",
    "MXU_SPAN_MAX",
]

LANE = 128  # TPU lane width

# Most recent comparison dispatch decision (op, engine, block shapes),
# recorded by the resolution helpers below.
# Engine/block resolution is host-side (never traced), so this is
# accurate per call; the ``CausalEngine`` front-door snapshots it into
# result metadata and the fleet benchmark records it so perf claims name
# the engine they measured.
LAST_DISPATCH: dict = {}

# Every kernel dispatch of this process, counted by (op, engine,
# interpret).  The jitted entry points (tick, merge_compare, the int32
# one-vs-many) count once per trace.  A run meant for the chip checks
# that no key ends in True.
DISPATCHES: collections.Counter = collections.Counter()


def _note_dispatch(op: str, engine: str, interpret: bool, **blocks) -> None:
    DISPATCHES[(op, engine, interpret)] += 1
    LAST_DISPATCH.clear()
    LAST_DISPATCH.update({"op": op, "engine": engine, **blocks})

# widest value span (max - min logical cell) the MXU thermometer engine
# accepts; FLOPs scale linearly with it, so wide windows go elementwise
MXU_SPAN_MAX = 64
_MXU_SPAN_BUCKETS = (8, 16, 32, 64)


def _row_align(interpret: bool, lanes: bool = False) -> int:
    """Row padding grain of a slab entering a kernel.  On the chip a u8
    tile needs 32 rows, and rows that become an output's lane axis (the
    tri engine's square blocks, a rectangle's columns) need 128; the
    interpreter only needs the 8-row sublane grain."""
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
    requested sizes that divide the padded shape.  Every kernel wrapper
    goes through this instead of re-deriving padding; callers crop
    results back to the original ``x.shape``.

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
def _classify_vs_many(
    q: jax.Array,            # [m] int32 local (query) logical cells
    peers: jax.Array,        # [N, m] int32 peer slab logical cells
    *,
    bn: int = 8,
    bm: int = 512,
    interpret: bool | None = None,
):
    """One-vs-many fused classify on an int32 slab (legacy layout).

    Returns dict with per-peer ``q_le_p`` / ``p_le_q`` dominance flags,
    total sums and Eq. 3 fp rates both directions.  Zero padding
    perturbs neither dominance nor sums; Eq. 3 uses the TRUE m.
    """
    interpret = resolve_interpret(interpret)
    DISPATCHES[("one_vs_many", "i32", interpret)] += 1
    (m,) = q.shape
    N, mp_ = peers.shape
    assert m == mp_, (q.shape, peers.shape)
    peers_p, bn_eff, bm_eff = tile2d(peers, bn, bm,
                                     row_align=_row_align(interpret))
    q_p = pad_to(q[None, :], peers_p.shape[1], axis=1)
    flags, sums, fp = bloom_one_vs_many_pallas(
        q_p, peers_p, bn=bn_eff, bm=bm_eff, m_true=m, interpret=interpret
    )
    return _classify_dict(flags, sums, fp, N)


def _classify_dict(flags, sums, fp, N):
    return {
        "q_le_p": flags[:N, 0].astype(bool),
        "p_le_q": flags[:N, 1].astype(bool),
        "sum_q": sums[0, 0],
        "sum_p": sums[:N, 1],
        "fp_q_before_p": fp[:N, 0],
        "fp_p_before_q": fp[:N, 1],
    }


def _one_vs_many_blocks(N: int, m: int, bn, bm, interpret: bool,
                        use_table: bool = True):
    """Resolve one-vs-many block defaults: explicit args > autotune >
    per-backend defaults.  The sharded wrapper resolves at FULL-N too,
    so both paths always tile the m axis identically."""
    if bn is None or bm is None:
        cfg = (autotune.lookup("one_vs_many", N, N, m, interpret) or {}) \
            if use_table else {}
        bn = bn or cfg.get("bn", 128 if interpret else 512)
        bm = bm or cfg.get("bm", 512)
    return bn, bm


def _one_vs_many_body(q, peers, base, bn, bm, m: int, interpret: bool):
    """Pad one packed slab (or one row shard of it) and run the kernel;
    shared by the unsharded and shard_map'ed classify paths."""
    nd = peers.shape[0]
    peers_p, bn_eff, bm_eff = tile2d(peers, bn, bm,
                                     row_align=_row_align(interpret))
    q_p = pad_to(q[None, :], peers_p.shape[1], axis=1)
    base_p = _pad_base(base, peers_p.shape[0])
    flags, sums, fp = bloom_one_vs_many_packed_pallas(
        q_p, peers_p, base_p, bn=bn_eff, bm=bm_eff, m_true=m,
        interpret=interpret)
    return flags[:nd], sums[:nd], fp[:nd]


def _classify_vs_many_packed(
    q: jax.Array,            # [m] int32 local (query) logical cells
    peers: jax.Array,        # [N, m] uint8 residual slab
    base: jax.Array,         # [N] (or [N, 1]) int32 per-slot offsets
    *,
    bn: int | None = None,
    bm: int | None = None,
    interpret: bool | None = None,
    use_autotune: bool = True,
):
    """One-vs-many classify against a PACKED slab: u8 HBM reads, the
    per-row base is re-applied tile-locally in VMEM.  Same result dict
    as ``_classify_vs_many``."""
    interpret = resolve_interpret(interpret)
    (m,) = q.shape
    N, mp_ = peers.shape
    assert m == mp_, (q.shape, peers.shape)
    bn, bm = _one_vs_many_blocks(N, m, bn, bm, interpret, use_autotune)
    _note_dispatch("one_vs_many", "packed", interpret, bn=bn, bm=bm)
    flags, sums, fp = _one_vs_many_body(q, peers, base, bn, bm, m, interpret)
    return _classify_dict(flags, sums, fp, N)


def _classify_vs_many_packed_sharded(
    q: jax.Array,            # [m] int32 local (query) logical cells
    peers: jax.Array,        # [N, m] uint8 residual slab, row-sharded
    base: jax.Array,         # [N] (or [N, 1]) int32 per-slot offsets
    *,
    mesh,                    # jax.sharding.Mesh carrying ``axis``
    axis: str,               # mesh axis the slab rows are sharded over
    bn: int | None = None,
    bm: int | None = None,
    interpret: bool | None = None,
    use_autotune: bool = True,
):
    """``_classify_vs_many_packed`` over a row-sharded slab via shard_map.

    The query is replicated; every device runs the packed one-vs-many
    Pallas kernel on its own ``[N/d, m]`` row shard — no cross-device
    traffic at all (the reduction is per-row).  Block shapes are
    resolved ONCE at full-N granularity so every shard count tiles the
    m axis identically: the f32 sum accumulation order (and therefore
    the Eq. 3 fp bits) is bit-identical across shard counts and vs the
    unsharded engine.
    """
    interpret = resolve_interpret(interpret)
    (m,) = q.shape
    N, mp_ = peers.shape
    assert m == mp_, (q.shape, peers.shape)
    shards = mesh.shape[axis]
    if N % shards:
        raise ValueError(f"slab rows {N} not divisible by {shards} shards")
    bn, bm = _one_vs_many_blocks(N, m, bn, bm, interpret, use_autotune)
    _note_dispatch("one_vs_many", "packed_sharded", interpret, bn=bn,
                   bm=bm, shards=shards)
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
        out_specs=(P(axis, None),) * 3,
        check_vma=False,     # no replication rule for pallas_call
    ))


def _overlay_wide_classify(out: dict, q: jax.Array, wide_idx,
                           wide_rows: jax.Array, *,
                           interpret: bool | None = None) -> dict:
    """Sparse promoted-row overlay for one-vs-many classify results.

    ``out`` is a packed-slab result dict whose promoted slots hold
    garbage (their u8 residuals were clipped at promotion); re-classify
    JUST the ``[P, m]`` promoted rows through the exact int32 kernel and
    patch them in.  The O(N) bulk stays packed — a single overflowed row
    no longer drops the whole slab compare to the int32 fallback.
    """
    wout = _classify_vs_many(q, wide_rows, interpret=interpret)
    idx = jnp.asarray(wide_idx, jnp.int32)
    patched = dict(out)
    for key in ("q_le_p", "p_le_q", "sum_p",
                "fp_q_before_p", "fp_p_before_q"):
        patched[key] = jnp.asarray(out[key]).at[idx].set(wout[key])
    return patched


# ---------------------------------------------------------------------------
# hybrid classify (exact hot rows + packed tail, one fused kernel)
# ---------------------------------------------------------------------------

def _hybrid_blocks(N: int, H: int, m: int, bn, bm, interpret: bool,
                   use_table: bool = True):
    """Resolve hybrid block defaults: explicit args > autotune (keyed on
    total rows AND hot count — the hot/tail split changes the winning
    tile) > per-backend defaults."""
    if bn is None or bm is None:
        cfg = (autotune.lookup("hybrid", N, H, m, interpret) or {}) \
            if use_table else {}
        bn = bn or cfg.get("bn", 128 if interpret else 512)
        bm = bm or cfg.get("bm", 512)
    return bn, bm


def _classify_hybrid(
    q: jax.Array,            # [m] int32 local (query) logical cells
    v_local: int,            # local-chain version V the hot rows are vs
    hot_meta: jax.Array,     # [H, 2] int32 (v, n_private) exact rows
    hot_sums: jax.Array,     # [H] (or [H, 1]) f32 shadow-row total sums
    tail: jax.Array,         # [T, m] uint8 residual slab
    tail_base: jax.Array,    # [T] (or [T, 1]) int32 per-slot offsets
    *,
    bn: int | None = None,
    bm: int | None = None,
    interpret: bool | None = None,
    use_autotune: bool = True,
):
    """One query vs an exact hot set PLUS a packed bloom tail, fused.

    Hot rows never touch bloom cells: their verdicts are integer
    compares of (v, n_private) chain coordinates against ``v_local`` —
    measured AND claimed fp are identically zero.  Tail rows run the
    packed one-vs-many math unchanged, so their verdicts/sums/fp stay
    bit-identical to a flat packed slab classified with the same bm.
    Returns the ``_classify_dict`` layout over H+T rows, hot first.
    """
    interpret = resolve_interpret(interpret)
    (m,) = q.shape
    H = hot_meta.shape[0]
    T, mt_ = tail.shape
    assert m == mt_, (q.shape, tail.shape)
    assert H > 0 and T > 0, "hybrid needs both a hot set and a tail " \
        "(route single-representation slabs through the plain engines)"
    bn, bm = _hybrid_blocks(H + T, H, m, bn, bm, interpret, use_autotune)
    tail_p, bn_eff, bm_eff = tile2d(tail, bn, bm,
                                    row_align=_row_align(interpret))
    q_p = pad_to(q[None, :], tail_p.shape[1], axis=1)
    base_p = _pad_base(tail_base, tail_p.shape[0])
    # pad hot rows to the tile grain with (v=0, n_private=0) filler —
    # cropped below, never observable
    meta_p = pad_to(jnp.asarray(hot_meta, jnp.int32), bn_eff, axis=0)
    hsum_p = pad_to(
        jnp.asarray(hot_sums, jnp.float32).reshape(-1, 1), bn_eff, axis=0)
    vloc = jnp.full((1, 1), v_local, jnp.int32)
    _note_dispatch("hybrid", "fused_hot_tail", interpret, bn=bn_eff,
                   bm=bm_eff, hot=H, tail=T)
    flags, sums, fp = bloom_hybrid_classify_pallas(
        q_p, vloc, meta_p, hsum_p, tail_p, base_p,
        bn=bn_eff, bm=bm_eff, m_true=m, interpret=interpret)
    Hp = meta_p.shape[0]
    flags = jnp.concatenate([flags[:H], flags[Hp:Hp + T]], axis=0)
    sums = jnp.concatenate([sums[:H], sums[Hp:Hp + T]], axis=0)
    fp = jnp.concatenate([fp[:H], fp[Hp:Hp + T]], axis=0)
    return _classify_dict(flags, sums, fp, H + T)


# ---------------------------------------------------------------------------
# all-pairs compare
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m_true",))
def _eq3_outer(row_sums, col_sums, m_true: int):
    """Eq. 3 fp of "row happened-before col" as an outer product — the
    reference expression every engine finalizes with."""
    return eq3_fp(row_sums[:, None], col_sums[None, :], m_true)


# public alias: the registry's sparse promoted-row assembly re-finalizes
# fp from corrected sums through the SAME jitted expression, keeping its
# values bit-identical to the in-engine finalize
eq3_outer = _eq3_outer


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
        "fp": _eq3_outer(row_sums, col_sums, m_true),
        "row_sums": row_sums,
        "col_sums": col_sums,
    }


def _matrix_blocks(engine, N, M, m, bi, bj, bm, interpret,
                   use_table: bool = True, shards: int = 1):
    """Resolve block shapes: explicit args > autotune table > defaults.

    Sharded resolution (``shards > 1``) consults the ``matrix_sharded``
    table entry keyed by the GLOBAL shape AND the shard count — never
    the plain ``matrix`` entry for the per-shard sub-shape — so a
    d-shard tune and a 1-shard tune whose shapes happen to collide can
    never poison each other's block choices."""
    if not use_table:
        cfg = {}
    elif shards > 1:
        cfg = autotune.lookup("matrix_sharded", N, M, m, interpret,
                              shards=shards) or {}
    else:
        cfg = autotune.lookup("matrix", N, M, m, interpret) or {}
    if shards == 1 and cfg.get("engine") != engine:
        cfg = {}
    # square 128 blocks: lane-aligned flag outputs on the chip, and the
    # 8-row int32 pairwise difference (8*bj*bm*4B) well inside VMEM; the
    # chip's thermometer streams 128-cell m-tiles
    dflt_bm = 128 if engine == "mxu" and not interpret else 512
    return (bi or cfg.get("bi", 128),
            bj or cfg.get("bj", 128),
            bm or cfg.get("bm", dflt_bm))


def _compare_matrix_packed(
    cells: jax.Array,           # [N, m] uint8 residual slab (rows)
    base: jax.Array,            # [N] (or [N, 1]) int32 per-slot offsets
    cols: jax.Array = None,     # [M, m] uint8 column slab; None -> symmetric
    col_base: jax.Array = None,
    *,
    engine: str | None = None,  # "tri" | "full" | "mxu" | None = auto
    bi: int | None = None,
    bj: int | None = None,
    bm: int | None = None,
    uniform_base: bool | None = None,
    interpret: bool | None = None,
    use_autotune: bool = True,
):
    """Tiled all-pairs compare over packed u8 slab(s).

    Symmetric calls (``cols is None``) sweep only the block-upper
    triangle and mirror the rest by transposition.  Returns the same
    dict as ``_compare_matrix``.
    """
    interpret = resolve_interpret(interpret)
    symmetric = cols is None
    if symmetric:
        cols, col_base = cells, base
    N, m = cells.shape
    M = cols.shape[0]
    if engine == "i32":
        # the legacy hint selects the int32 kernel in _compare_matrix;
        # a packed slab has no int32 kernel, so resolve to auto (flags
        # are exact under every packed engine) instead of raising —
        # registry.all_pairs(**kw) call sites keep working packed
        engine = None
    if engine is None:
        cfg = (autotune.lookup("matrix", N, M, m, interpret) or {}) \
            if use_autotune else {}
        engine = cfg.get("engine", "tri")
        if engine == "i32":
            engine = "tri"
        if engine == "mxu" and not _mxu_viable(cells, base, cols, col_base):
            engine = "tri"
    if engine == "tri" and not symmetric:
        engine = "full"
    if uniform_base is None:
        b = jnp.asarray(base).reshape(-1)
        cb = jnp.asarray(col_base).reshape(-1)
        uniform_base = bool((b == b[0]).all()) and bool((cb == b[0]).all())
    bi, bj, bm = _matrix_blocks(engine, N, M, m, bi, bj, bm, interpret,
                                use_autotune)
    _note_dispatch("matrix", engine, interpret, bi=bi, bj=bj, bm=bm)

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
        lo, span = _logical_bounds(cells, base, cols, col_base)
        n_thr = _span_bucket(span)
        rows_p, cols_p, bi_eff, bj_eff, bm_eff = _rect_tiles(
            cells, cols, bi, bj, bm, interpret)
        viol = bloom_matrix_mxu_pallas(
            rows_p, cols_p, _pad_base(base, rows_p.shape[0]),
            _pad_base(col_base, cols_p.shape[0]),
            n_thresholds=n_thr, lo=lo,
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


def _compare_matrix_packed_sharded(
    cells: jax.Array,           # [N, m] uint8 residual slab, row-sharded
    base: jax.Array,            # [N] (or [N, 1]) int32 per-slot offsets
    *,
    mesh,                       # jax.sharding.Mesh carrying ``axis``
    axis: str,                  # mesh axis the slab rows are sharded over
    engine: str | None = None,  # engine HINT; the ring resolves to "full"
    strategy: str | None = None,   # "ring" | "replicated" | None = table
    bi: int | None = None,
    bj: int | None = None,
    bm: int | None = None,
    uniform_base: bool | None = None,
    interpret: bool | None = None,
    use_autotune: bool = True,
    mesh_outputs: bool = True,
):
    """Symmetric all-pairs over a row-sharded packed slab.

    Two strategies, dispatched per shape from the autotune table's
    ``matrix_sharded`` entry (explicit ``strategy`` wins; default
    ``ring`` when the table is silent):

    ``ring`` — each of the ``d`` devices holds a ``[N/d, m]`` row shard
    and circulates a column shard around the mesh ring with
    ``ppermute``; every ring step compares its resident rows against
    the visiting columns, filling one ``[N/d, N/d]`` block of its
    ``[N/d, N]`` block-row.  The sweep is HALVED by symmetry: only
    ceil(d/2) visiting offsets are computed, and each off-diagonal
    block ships its transposed flags back across the ring
    (``le(j, i) == ge(i, j)^T``) to fill the mirror block.  Since PR 7
    the ring is also: DOUBLE-BUFFERED (the ppermute for step s+1 is
    issued before the compute on step s, so communication overlaps
    compute on real meshes); TRIANGLE-swept on the diagonal step (the
    resident-vs-resident block is symmetric, so the tri engine sweeps
    its upper half and mirrors locally); and DEDUPLICATED on the even-d
    half-way offset (only devices ``i < d/2`` run the kernel; the
    mirror halves arrive by a partial ppermute of the transposed
    flags).  Per-device work is the single-device triangle divided by
    d, so the ring wins wherever devices compute in parallel.

    ``replicated`` — don't shard the compare at all: gather the packed
    slab (u8 residuals + int32 bases, the cheapest representation to
    ship) onto one mesh device and run the plain single-device triangle
    engine there.  No per-step collectives and no SPMD program; this
    wins where mesh devices are time-sliced onto the same host cores
    (forced-host CI meshes) and ring collectives buy no parallelism —
    exactly what the autotuner's cost model predicts and its measured
    sweep confirms per backend.

    Both strategies are bit-identical to the unsharded sweep: flags are
    exact (mirroring moves bits, it never recomputes them; replication
    runs the very same kernel), and the fp / sums finalize runs through
    the SAME ``_eq3_outer`` / ``_packed_row_sums`` expressions.

    Pass ``uniform_base`` explicitly on hot paths (the registry does,
    from its host-side base copy): the default probes the sharded base
    vector, which costs a cross-device reduction plus a blocking host
    sync per call.

    ``mesh_outputs`` (default True) guarantees the result arrays are
    row-sharded over the mesh whatever strategy ran — required whenever
    the caller combines them with other mesh-sharded arrays (dead-slot
    masks, promoted-row overlays).  Callers that hand the dict straight
    back (the fully-alive packed fast path) pass False so the
    replicated strategy skips a pointless [N, N] x 4 reshard.
    """
    interpret = resolve_interpret(interpret)
    # every engine name valid elsewhere is accepted so sharding a
    # registry never breaks existing all_pairs(**kw) call sites: "tri"
    # has no per-tile meaning on the ring (off-diagonal tiles are
    # rectangles), "mxu" would need a host-synced global span probe,
    # and "i32" is the legacy-kernel hint from _compare_matrix — all
    # resolve to the packed tri/rect engines, whose flags are exact
    if engine not in (None, "full", "tri", "mxu", "i32"):
        raise ValueError(f"unknown packed engine: {engine}")
    N, m = cells.shape
    d = mesh.shape[axis]
    if N % d:
        raise ValueError(f"slab rows {N} not divisible by {d} shards")
    # keep the caller's array object when already normalized — the
    # replicated branch memoizes the cross-device copy by identity
    if not (isinstance(base, jax.Array) and base.dtype == jnp.int32
            and base.ndim == 1):
        base = jnp.asarray(base, jnp.int32).reshape(-1)
    if uniform_base is None:
        b = base
        uniform_base = bool((b == b[0]).all())
    with_base = not uniform_base
    if strategy is None:
        cfg = (autotune.lookup("matrix_sharded", N, N, m, interpret,
                               shards=d) or {}) if use_autotune else {}
        strategy = cfg.get("strategy", "ring")
    if strategy == "replicated":
        dev = mesh.devices.flat[0]
        cells_g = _gathered_replica(cells, dev)
        base_g = _gathered_replica(base, dev)
        out = _compare_matrix_packed(
            cells_g, base_g, bi=bi, bj=bj, bm=bm,
            uniform_base=uniform_base, interpret=interpret,
            use_autotune=use_autotune)
        inner = dict(LAST_DISPATCH)
        if mesh_outputs:
            # hand back the ring's placement contract: [N, N] matrices
            # row-sharded over the mesh, [N] sums sharded — downstream
            # masking/overlay code must not see single-device commitments
            out = {k: jax.device_put(v, NamedSharding(
                       mesh, P(axis, None) if v.ndim == 2 else P(axis)))
                   for k, v in out.items()}
        _note_dispatch("matrix",
                       f"replicated_{inner.get('engine', 'tri')}",
                       interpret, bi=inner.get("bi"), bj=inner.get("bj"),
                       bm=inner.get("bm"), shards=d, strategy="replicated")
        return out
    if strategy != "ring":
        raise ValueError(f"unknown sharded strategy: {strategy}")
    bi, bj, bm = _matrix_blocks("full", N, N, m, bi, bj, bm,
                                interpret, use_autotune, shards=d)
    _note_dispatch("matrix", "ring_full", interpret, bi=bi, bj=bj, bm=bm,
                   shards=d, strategy="ring")
    fn = _sharded_ring_fn(mesh, axis, N, bi, bj, bm, m, with_base, interpret)
    le, ge = fn(cells, base)
    row_sums = _packed_row_sums(cells, base, m)
    return _matrix_dict(le.astype(bool), ge.astype(bool),
                        row_sums, row_sums, m)


# gather memo for the "replicated" sharded strategy: registries call
# all_pairs repeatedly on the SAME slab array, so the cross-device copy
# is paid once per slab, not per call.  Keyed on object identity and
# guarded by a strong reference to the keyed array itself — an id can't
# be reused while the cache still holds the object it identifies.
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

    Three PR 7 refinements on top:

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


def _logical_bounds(cells, base, cols, col_base):
    """Eager (host-synced) global [lo, hi] logical value bounds."""
    b = jnp.asarray(base, jnp.int32).reshape(-1)
    cb = jnp.asarray(col_base, jnp.int32).reshape(-1)
    lo = int(jnp.minimum(b.min(), cb.min()))
    hi = int(jnp.maximum(
        (cells.astype(jnp.int32).max(axis=1) + b).max(),
        (cols.astype(jnp.int32).max(axis=1) + cb).max()))
    return lo, hi - lo


def _mxu_viable(cells, base, cols, col_base) -> bool:
    """The thermometer needs a concrete value span: under an outer jit
    the host-synced bounds probe cannot run, so the engine is out."""
    if isinstance(cells, jax.core.Tracer):
        return False
    _, span = _logical_bounds(cells, base, cols, col_base)
    return span <= MXU_SPAN_MAX


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


def _compare_matrix(
    rows: jax.Array,         # [N, m] int32 logical cells
    cols: jax.Array,         # [M, m] int32 logical cells
    *,
    engine: str | None = None,   # None = auto; "i32" forces legacy kernel
    bi: int | None = None,
    bj: int | None = None,
    bm: int | None = None,
    interpret: bool | None = None,
    use_autotune: bool = True,
):
    """Tiled all-pairs compare: drop-in for the broadcast reference
    ``repro.core.clock.comparability_matrix`` without the O(n^2 * m)
    materialization.

    Auto engine: when the global value span fits a byte the slab is
    packed on the fly (shared window base -> uniform-base fast path) and
    compared by the packed engines — the symmetric triangle sweep when
    ``rows is cols``.  Wider spans fall back to the int32 kernel.

    Returns dict with [N, M] ``a_le_b`` / ``b_le_a`` / ``concurrent``
    flag matrices, the Eq. 3 ``fp`` of "row before col", and the
    per-row / per-col sums.
    """
    interpret = resolve_interpret(interpret)
    symmetric = rows is cols
    N, m = rows.shape
    M, mc = cols.shape
    assert m == mc, (rows.shape, cols.shape)

    if engine is None and isinstance(rows, jax.core.Tracer):
        engine = "i32"      # under an outer jit the span probe can't sync
    if engine is None and use_autotune:
        # honor a measured "int32 wins here" verdict before paying the probe
        cfg = autotune.lookup("matrix", N, M, m, interpret) or {}
        if cfg.get("engine") == "i32":
            engine = "i32"
    if engine != "i32":
        lo, hi = (int(v) for v in jax.device_get(
            _span_probe(rows, None if symmetric else cols)))
        if hi - lo <= U8_MAX:
            packed_rows = _shift_pack(rows, lo)
            base = jnp.full((N,), lo, jnp.int32)
            if symmetric:
                return _compare_matrix_packed(
                    packed_rows, base, engine=engine, bi=bi, bj=bj, bm=bm,
                    uniform_base=True, interpret=interpret,
                    use_autotune=use_autotune)
            return _compare_matrix_packed(
                packed_rows, base, _shift_pack(cols, lo),
                jnp.full((M,), lo, jnp.int32), engine=engine,
                bi=bi, bj=bj, bm=bm, uniform_base=True, interpret=interpret,
                use_autotune=use_autotune)
        if engine is not None:
            raise ValueError(
                f"engine={engine} needs value span <= {U8_MAX}, got {hi - lo}")

    bi, bj, bm = _matrix_blocks("i32", N, M, m, bi, bj, bm, interpret,
                                use_autotune)
    _note_dispatch("matrix", "i32", interpret, bi=bi, bj=bj, bm=bm)
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


@functools.partial(jax.jit, static_argnames=("lo",))
def _shift_pack(x, lo: int):
    return (jnp.asarray(x, jnp.int32) - lo).astype(jnp.uint8)


@jax.jit
def _span_probe(rows, cols=None):
    """[lo, hi] over one or two slabs, fetched in ONE host transfer."""
    lo, hi = jnp.min(rows), jnp.max(rows)
    if cols is not None:
        lo = jnp.minimum(lo, jnp.min(cols))
        hi = jnp.maximum(hi, jnp.max(cols))
    return jnp.stack([lo, hi])


# ---------------------------------------------------------------------------
# deprecated pre-front-door entry points
# ---------------------------------------------------------------------------

def _shim(name: str, impl):
    """Thin ``DeprecationWarning`` shim: delegates to the SAME
    implementation the ``repro.causal.CausalEngine`` front-door calls,
    so shim results are bit-identical to the new API by construction.
    The warning is attributed to the CALLER's module (stacklevel=2) so
    CI can gate ``error::DeprecationWarning`` on ``repro.*`` modules,
    proving no internal caller still uses these."""
    def wrapper(*args, **kwargs):
        warnings.warn(
            f"repro.kernels.ops.{name} is deprecated; use the "
            "repro.causal.CausalEngine front-door "
            "(engine.classify / engine.pairs) instead",
            DeprecationWarning, stacklevel=2)
        return impl(*args, **kwargs)
    wrapper.__name__ = wrapper.__qualname__ = name
    wrapper.__doc__ = ("DEPRECATED — use ``repro.causal.CausalEngine``.\n\n"
                       + (getattr(impl, "__doc__", None) or ""))
    return wrapper


compare_matrix = _shim("compare_matrix", _compare_matrix)
compare_matrix_packed = _shim("compare_matrix_packed", _compare_matrix_packed)
compare_matrix_packed_sharded = _shim(
    "compare_matrix_packed_sharded", _compare_matrix_packed_sharded)
classify_vs_many = _shim("classify_vs_many", _classify_vs_many)
classify_vs_many_packed = _shim(
    "classify_vs_many_packed", _classify_vs_many_packed)
classify_vs_many_packed_sharded = _shim(
    "classify_vs_many_packed_sharded", _classify_vs_many_packed_sharded)
overlay_wide_classify = _shim("overlay_wide_classify", _overlay_wide_classify)
