"""Parameterized compare-kernel template: ONE design, every engine.

The hand-rolled Pallas engines in the old ``bloom_matrix.py`` (symmetric
triangle, full rectangle, MXU thermometer, one-vs-many — each in packed
u8 and/or int32 flavors) had converged on one shape: stream m-tiles of
one or two operand slabs through VMEM and reduce a per-tile dominance
predicate into revisited output blocks.  This module is that design
written once, parameterized by a ``CompareSpec``.  The stats engines
emit sums, and Eq. 3 is applied to them after the kernel by the one
reference expression (``kernels.ref.eq3_fp``):

    topology        "tri" (block-upper-triangle sweep over one slab),
                    "rect" (full rectangle, rows x cols),
                    "mxu" (thermometer dot_general violation counts),
                    "one_vs_many" (one query row vs a peer slab),
                    "hybrid" (one query vs exact hot rows + packed tail
                    in ONE grid: leading row-tiles answer from exact
                    (v, n_private) chain coordinates with fp pinned to
                    0.0, trailing tiles run the unmodified packed
                    one-vs-many math so tail verdicts stay bit-identical
                    to the flat slab)
    pack            "u8" (quantized residuals + per-row int32 base) or
                    "i32" (logical cells)
    bi / bj / bm    block shapes (bi doubles as bn for one_vs_many)
    pipeline_depth  pallas pipeline staging: >= 2 marks the revisit-free
                    grid axes "parallel" so Mosaic double-buffers
                    operand tiles; 1 pins every axis "arbitrary"
    acc             flag accumulator dtype ("int8" / "int32"; None =
                    the topology's pinned default)
    with_base       fold per-row window bases into the tile difference
    with_stats      emit sums + Eq. 3 fp outputs alongside flags
    n_thresholds    MXU value-span budget T (thermometer width)

``emit(spec)`` validates the spec and returns a jitted wrapper whose
outputs are BIT-IDENTICAL to the hand-rolled kernel the spec names
(pinned by tests/test_template.py against verbatim copies of the
pre-refactor kernels).  The one-vs-many and hybrid instances read
per-row vectors (bases, hot metadata) and write their results as dense
rows, ``[1|2, N]`` with the row index along lanes: their ``[2, N]``
results are the legacy ``[N, 2]`` ones transposed, bit for bit.
``kernels.generate`` builds the named engine instances the rest of the
system imports; nothing outside this pair defines a kernel body
anymore.

The generator refuses, at emission/call time, any knob combination
whose per-grid-step VMEM estimate (``vmem_estimate``) exceeds the
backend budget — the same analytic model the cost-model autotuner uses
to prune its search space (``kernels.autotune.predict_cost``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import eq3_fp

__all__ = [
    "CompareSpec",
    "emit",
    "validate",
    "vmem_estimate",
    "VMEM_BUDGET",
    "resolve_interpret",
    "TOPOLOGIES",
    "PACKS",
]

TOPOLOGIES = ("tri", "rect", "mxu", "one_vs_many", "hybrid")
PACKS = ("u8", "i32")
_ACCS = ("int8", "int32")

# Per-grid-step VMEM budget (bytes).  The tpu figure is the scoped VMEM
# Mosaic grants a kernel by default on v5e; ``vmem_estimate`` is
# conservative against it (compiled for v5e: specs estimated at 20 MiB
# fit, at 40 MiB they ran out of VMEM).  Interpret mode has no VMEM, but
# the same model bounds host scratch so emitted specs stay sane.
VMEM_BUDGET = {"tpu": 16 * 2**20, "interpret": 512 * 2**20}

@dataclasses.dataclass(frozen=True)
class CompareSpec:
    """One point in the compare-kernel design space (see module doc)."""

    topology: str
    pack: str = "u8"
    bi: int = 128
    bj: int = 128
    bm: int = 512
    pipeline_depth: int = 2
    acc: Optional[str] = None
    with_base: bool = False
    with_stats: bool = False
    n_thresholds: int = 0

    @property
    def acc_dtype(self):
        if self.topology == "mxu":
            return jnp.float32
        if self.acc is not None:
            return {"int8": jnp.int8, "int32": jnp.int32}[self.acc]
        # pinned defaults: what the hand-rolled kernels accumulated in
        if self.topology in ("one_vs_many", "hybrid") or self.pack == "i32":
            return jnp.int32
        return jnp.int8

    def label(self) -> str:
        parts = [self.topology, self.pack,
                 f"bi{self.bi}", f"bj{self.bj}", f"bm{self.bm}",
                 f"pd{self.pipeline_depth}"]
        if self.with_base:
            parts.append("base")
        if self.n_thresholds:
            parts.append(f"T{self.n_thresholds}")
        return "/".join(parts)


def kernel_name(spec: CompareSpec) -> str:
    """The ``pallas_call`` name of ``spec``'s kernel: its HLO operation
    and device-trace event carry it, the same for every block shape."""
    return f"bloom_{spec.topology}_{spec.pack}"


def validate(spec: CompareSpec, backend: str | None = None) -> None:
    """Refuse malformed or over-budget specs (raises ValueError)."""
    if spec.topology not in TOPOLOGIES:
        raise ValueError(f"unknown topology {spec.topology!r}")
    if spec.pack not in PACKS:
        raise ValueError(f"unknown pack mode {spec.pack!r}")
    if spec.acc is not None and spec.acc not in _ACCS:
        raise ValueError(f"unknown accumulator {spec.acc!r}")
    if spec.bi % 8 or spec.bj % 8:
        raise ValueError(f"row blocks must be sublane multiples: "
                         f"bi={spec.bi} bj={spec.bj}")
    if spec.bm % 128:
        raise ValueError(f"bm must be a lane multiple: bm={spec.bm}")
    if spec.pipeline_depth not in (1, 2, 3):
        raise ValueError(f"pipeline_depth must be 1..3, "
                         f"got {spec.pipeline_depth}")
    if spec.topology == "tri" and spec.pack != "u8":
        raise ValueError("tri topology is packed-only (pack='u8')")
    if spec.topology == "mxu":
        if spec.pack != "u8":
            raise ValueError("mxu topology is packed-only (pack='u8')")
        if spec.n_thresholds < 1:
            raise ValueError("mxu needs n_thresholds >= 1")
        if spec.with_stats:
            raise ValueError("mxu emits violation counts, not stats")
    elif spec.n_thresholds:
        raise ValueError("n_thresholds is an mxu-only knob")
    if spec.topology == "one_vs_many" and not spec.with_stats:
        raise ValueError("one_vs_many always emits stats (flags+sums+fp)")
    if spec.topology == "hybrid":
        if spec.pack != "u8":
            raise ValueError("hybrid's tail slab is packed-only "
                             "(pack='u8'); hot rows carry no cells at all")
        if not (spec.with_stats and spec.with_base):
            raise ValueError("hybrid always emits stats and folds tail "
                             "bases (with_stats=True, with_base=True)")
    if spec.topology == "rect" and spec.pack == "i32" and not spec.with_stats:
        raise ValueError("rect/i32 is the stats engine (with_stats=True)")
    if spec.with_stats and spec.topology in ("tri", "rect") \
            and spec.pack == "u8":
        raise ValueError("packed tri/rect emit flags only; sums/fp are "
                         "finalized outside the kernel")
    if backend is not None:
        need = vmem_estimate(spec)
        budget = VMEM_BUDGET[backend]
        if need > budget:
            raise ValueError(
                f"VMEM estimate {need} B exceeds the {backend} budget "
                f"{budget} B for {spec.label()}")


def vmem_estimate(spec: CompareSpec) -> int:
    """Peak per-grid-step working set (bytes) of one emitted instance.

    Operand tiles are multiplied by the pipeline depth (Mosaic keeps
    ``depth`` tiles in flight when axes are parallel); output blocks are
    double-buffered; int32 intermediates and scratch are single-buffered.
    Narrow ``[rows, 1|2]`` blocks and scratch columns occupy whole
    128-lane rows in VMEM; ``[1|2, cols]`` row blocks occupy 8 sublanes.
    """
    bi, bj, bm, d = spec.bi, spec.bj, spec.bm, spec.pipeline_depth
    if spec.topology in ("one_vs_many", "hybrid"):
        esize = 1 if spec.pack == "u8" else 4
        row = _SUBLANES * bi * 4                      # one [1|2, bn] block
        # query row, peer tile, base row
        operands = _SUBLANES * bm * 4 + bi * bm * esize + row
        if spec.topology == "hybrid":
            operands += 2 * row                       # meta + hot-sum rows
        work = 3 * bi * bm * 4         # widened tile, difference, mask
        # base column, flag and sum_p accumulators, the sum_q cell
        scratch = 3 * bi * _LANES * 4 + _SUBLANES * _LANES * 4
        outputs = 2 * 2 * row                         # flags + sums rows
        return operands * d + work + scratch + outputs
    if spec.topology == "mxu":
        operands = (bi + bj) * bm + (bi + bj) * _LANES * 4
        # widened values + one threshold's f32 encodings
        work = 2 * (bi + bj) * bm * 4
        return operands * d + work + 2 * bi * bj * 4
    # tri / rect: an 8-row chunk of the left tile vs the whole right
    # tile, as an int32 difference and its lane-masked copy
    esize = 1 if spec.pack == "u8" else 4
    operands = (bi + bj) * bm * esize
    if spec.with_base:
        operands += bi * _LANES * 4 + 8 * bj * 4
    work = bj * bm * 4 + 2 * _CHUNK * bj * bm * 4
    acc = jnp.dtype(spec.acc_dtype).itemsize
    outputs = 2 * 2 * bi * bj * acc
    if spec.with_stats:
        outputs += 2 * bi * _LANES * 4 + 2 * 8 * bj * 4  # row / col sums
    return operands * d + work + outputs


def _backend(interpret: bool) -> str:
    return "interpret" if interpret else "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """The one place ``interpret=None`` (auto) is decided: Pallas runs
    compiled on a TPU backend and in the interpreter anywhere else.
    Every dispatch records the resolved value (``ops.DISPATCHES``), so a
    run that meant to use the chip can prove none of its kernels fell
    back to the interpreter."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _compiler_params(spec: CompareSpec, n_axes: int, interpret: bool):
    """dimension_semantics from the pipeline-depth knob (TPU only).

    Revisit-free axes go "parallel" at depth >= 2 so Mosaic pipelines
    operand fetches; the m-tile axis (and the tri sweep axis, whose
    index map is scalar-prefetch driven) stays "arbitrary", as does the
    column axis of the i32 stats engine, whose row-sum block is
    revisited across it.
    """
    if interpret:
        return {}
    if spec.pipeline_depth < 2 or spec.topology == "tri":
        sem = ("arbitrary",) * n_axes
    elif spec.topology == "rect" and spec.with_stats:
        sem = ("parallel",) + ("arbitrary",) * (n_axes - 1)
    else:
        sem = ("parallel",) * (n_axes - 1) + ("arbitrary",)
    return {"compiler_params": pltpu.CompilerParams(dimension_semantics=sem)}


# ---------------------------------------------------------------------------
# shared body pieces
#
# Mosaic cannot relayout or reduce bool vectors, so no bool value is
# reduced, concatenated or written: reductions run over int32, and every
# flag is an int produced by a select (``_flag``).  Eq. 3 is finalized
# outside the kernels (``_eq3_pairs`` / ``ref.eq3_fp``).
# ---------------------------------------------------------------------------

_LANES = 128
_SUBLANES = 8
_CHUNK = 8                 # left-tile rows per pairwise difference
_PAD_LO, _PAD_HI = -(1 << 30), 1 << 30   # neutral values for max / min


def _flag(pred, dtype):
    """0/1 of ``pred`` in ``dtype``, built by a select."""
    return jnp.where(pred, 1, 0).astype(dtype)


def _two_lanes(x0, x1):
    """[rows, 2] from two [rows|1, 1] columns, without a concatenate."""
    rows = max(x0.shape[0], x1.shape[0])
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 2), 1)
    return jnp.where(lane == 0, x0, x1)


def _two_rows(x0, x1):
    """[2, cols] from two [1, cols|1] rows, without a concatenate."""
    cols = max(x0.shape[1], x1.shape[1])
    row = jax.lax.broadcasted_iota(jnp.int32, (2, cols), 0)
    return jnp.where(row == 0, x0, x1)


def _accumulate(j, flags_ref, sums_ref, flags, sums):
    """AND flags / add sums across m-tiles into revisited blocks (or
    VMEM scratch)."""
    @pl.when(j == 0)
    def _init():
        flags_ref[...] = flags
        sums_ref[...] = sums

    @pl.when(j > 0)
    def _acc():
        flags_ref[...] = flags_ref[...] & flags
        sums_ref[...] = sums_ref[...] + sums


def _and_into(jm, ref, rows, val):
    """AND-accumulate ``val`` into ``ref[rows]`` across m-tiles."""
    @pl.when(jm == 0)
    def _init():
        ref[rows, :] = val

    @pl.when(jm > 0)
    def _acc():
        ref[rows, :] = ref[rows, :] & val


def _pairwise_flags(jm, a_ref, b, le_ref, ge_ref, acc, *,
                    valid=None, delta=None):
    """[bi, bj] (le, ge) of the left tile vs ``b`` ([bj, bm] int32).

    ``le(i, j) = max_m(a_im - b_jm) <= 0`` and ``ge = min(...) >= 0``,
    both from ONE int32 wrap-subtraction (bounded-counter semantics, as
    ``core.clock.ordering``).  The left tile is swept in 8-row chunks so
    the [8, bj, bm] difference stays small.  ``valid`` ([1, 1, bm])
    masks pad lanes out of both extremes; ``delta(rows)`` is a [8, bj]
    per-pair offset added after the reduction (the packed base delta —
    constant along m, so adding it to the extreme is exact)."""
    def chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * _CHUNK, _CHUNK), _CHUNK)
        a = a_ref[rows, :].astype(jnp.int32)
        d = a[:, None, :] - b[None, :, :]
        if valid is None:
            mx, mn = jnp.max(d, axis=2), jnp.min(d, axis=2)
        else:
            mx = jnp.max(jnp.where(valid, d, _PAD_LO), axis=2)
            mn = jnp.min(jnp.where(valid, d, _PAD_HI), axis=2)
        if delta is not None:
            off = delta(rows)
            mx, mn = mx + off, mn + off
        _and_into(jm, le_ref, rows, _flag(mx <= 0, acc))
        _and_into(jm, ge_ref, rows, _flag(mn >= 0, acc))
        return carry

    jax.lax.fori_loop(0, a_ref.shape[0] // _CHUNK, chunk, 0)


def _packed_flags_step(refs, *, jm, with_base, m_true, bm, acc):
    """Shared body of the packed tri/rect flag kernels.

    With bases, the pair offset is the int32 wrap-subtraction of the two
    row bases clipped to ±(U8_MAX + 1): any |delta| beyond the residual
    range forces the verdict, so the clip preserves verdicts exactly and
    two near-wrap packed rows compare through their true signed gap.
    Pad lanes (zero residuals) are only neutral when bases cancel, so
    they are masked out of the extremes."""
    if with_base:
        a_ref, b_ref, abase_ref, bbase_ref, le_ref, ge_ref = refs
    else:
        a_ref, b_ref, le_ref, ge_ref = refs
    b = b_ref[...].astype(jnp.int32)
    if not with_base:
        _pairwise_flags(jm, a_ref, b, le_ref, ge_ref, acc)
        return
    bbase = bbase_ref[...]                             # [1, bj] row
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, bm), 2) + jm * bm
    _pairwise_flags(
        jm, a_ref, b, le_ref, ge_ref, acc, valid=col < m_true,
        delta=lambda rows: jnp.clip(abase_ref[rows, :] - bbase, -256, 256))


def _one_vs_many_flags(q, p, acc):
    """[bn, 2] (q <= p, p <= q) flags of one query vs a peer tile, from
    the int32 wrap-subtraction ``p - q`` (bit-identical to direct
    compares in the sane range, correct across the int32 wrap point)."""
    d = p - q
    return _two_lanes(_flag(jnp.min(d, axis=1, keepdims=True) >= 0, acc),
                      _flag(jnp.max(d, axis=1, keepdims=True) <= 0, acc))


def _row_sum(x):
    return jnp.sum(x, axis=1, keepdims=True).astype(jnp.float32)


def _eq3_pairs(sums, m: int, axis: int = 1):
    """Eq. 3 fp (q before p, p before q) from the total sums (sum_q,
    sum_p) stacked along ``axis``: ``[N, 2]`` pairs (axis 1) or ``[2,
    N]`` dense rows (axis 0) — the reference expression, applied once
    by XLA."""
    sq = jax.lax.index_in_dim(sums, 0, axis, keepdims=False)
    sp = jax.lax.index_in_dim(sums, 1, axis, keepdims=False)
    return jnp.stack([eq3_fp(sq, sp, m), eq3_fp(sp, sq, m)], axis=axis)


def _rows_step(j, n_mtiles, scratch, flags_ref, sums_ref, flags, sq, sp):
    """One m-tile of a one-vs-many row block, written as dense rows.

    ``flags`` ([bn, 2]) AND-accumulates and ``sp`` ([bn, 1] sum_p) adds
    into VMEM scratch columns, ``sq`` ([1, 1] sum_q) into one scratch
    cell: the same int32 reductions and f32 additions, in the same
    order, as a revisited ``[bn, 2]`` output block.  At the last m-tile
    one transpose turns the columns into the ``(2, bn)`` output blocks
    (flags: q ≼ p, p ≼ q; sums: sum_q, sum_p), so no per-row vector
    crosses the kernel boundary padded to 128 lanes."""
    flags_acc, sq_acc, sp_acc = scratch
    _accumulate(j, flags_acc, sp_acc, flags, sp)
    _accumulate_sq(j, sq_acc, sq)

    @pl.when(j == n_mtiles - 1)
    def _store():
        flags_ref[...] = flags_acc[...].T
        sums_ref[...] = _two_rows(sq_acc[...], sp_acc[...].T)


def _accumulate_sq(j, sq_acc, sq):
    """Add one m-tile's [1, 1] sum_q into its scratch cell."""
    @pl.when(j == 0)
    def _init():
        sq_acc[...] = sq

    @pl.when(j > 0)
    def _acc():
        sq_acc[...] = sq_acc[...] + sq


def _rows_scratch(bn: int, acc) -> list:
    """VMEM scratch of ``_rows_step``: flag and sum_p columns, the sum_q
    cell."""
    return [pltpu.VMEM((bn, 2), acc), pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32)]


# ---------------------------------------------------------------------------
# per-topology emitters
# ---------------------------------------------------------------------------

def _emit_tri(spec: CompareSpec):
    bi, bm, with_base = spec.bi, spec.bm, spec.with_base
    acc = spec.acc_dtype

    def kernel(ti_ref, tj_ref, *refs, m_true):
        _packed_flags_step(refs, jm=pl.program_id(1), with_base=with_base,
                           m_true=m_true, bm=bm, acc=acc)

    @functools.partial(jax.jit, static_argnames=("m_true", "interpret"))
    def tri_pallas(cells, base, *, m_true=None, interpret=False):
        """Symmetric all-pairs over one packed slab (upper triangle).

        Returns (le, ge) [N, N] valid ONLY in block-upper-triangle
        positions; the caller mirrors the rest by transposition."""
        validate(spec, _backend(interpret))
        N, m = cells.shape
        assert N % bi == 0 and m % bm == 0, (N, m, bi, bm)
        k = N // bi
        tri = [(i, j) for i in range(k) for j in range(i, k)]
        ti = jnp.asarray([i for i, _ in tri], jnp.int32)
        tj = jnp.asarray([j for _, j in tri], jnp.int32)
        n_mtiles = m // bm
        body = functools.partial(kernel, m_true=m_true if m_true else m)
        in_specs = [
            pl.BlockSpec((bi, bm), lambda t, jm, ti, tj: (ti[t], jm)),
            pl.BlockSpec((bi, bm), lambda t, jm, ti, tj: (tj[t], jm)),
        ]
        operands = [cells, cells]
        if with_base:
            in_specs += [
                pl.BlockSpec((bi, 1), lambda t, jm, ti, tj: (ti[t], 0)),
                pl.BlockSpec((1, bi), lambda t, jm, ti, tj: (0, tj[t])),
            ]
            operands += [base, base.reshape(1, N)]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(len(tri), n_mtiles),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((bi, bi), lambda t, jm, ti, tj: (ti[t], tj[t])),
                pl.BlockSpec((bi, bi), lambda t, jm, ti, tj: (ti[t], tj[t])),
            ],
        )
        le, ge = pl.pallas_call(
            body,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((N, N), acc),
                jax.ShapeDtypeStruct((N, N), acc),
            ],
            interpret=interpret,
            name=kernel_name(spec),
            **_compiler_params(spec, 2, interpret),
        )(ti, tj, *operands)
        return le, ge

    return tri_pallas


def _emit_rect_u8(spec: CompareSpec):
    bi, bj, bm, with_base = spec.bi, spec.bj, spec.bm, spec.with_base
    acc = spec.acc_dtype

    def kernel(*refs, m_true):
        _packed_flags_step(refs, jm=pl.program_id(2), with_base=with_base,
                           m_true=m_true, bm=bm, acc=acc)

    @functools.partial(jax.jit, static_argnames=("m_true", "interpret"))
    def rect_pallas(rows, cols, row_base, col_base, *,
                    m_true=None, interpret=False):
        """Full-rectangle packed compare: (le, ge) [N, M]."""
        validate(spec, _backend(interpret))
        N, m = rows.shape
        M, mc = cols.shape
        assert m == mc and N % bi == 0 and M % bj == 0 and m % bm == 0
        n_mtiles = m // bm
        body = functools.partial(kernel, m_true=m_true if m_true else m)
        in_specs = [
            pl.BlockSpec((bi, bm), lambda i, j, jm: (i, jm)),
            pl.BlockSpec((bj, bm), lambda i, j, jm: (j, jm)),
        ]
        operands = [rows, cols]
        if with_base:
            in_specs += [
                pl.BlockSpec((bi, 1), lambda i, j, jm: (i, 0)),
                pl.BlockSpec((1, bj), lambda i, j, jm: (0, j)),
            ]
            operands += [row_base, col_base.reshape(1, M)]
        le, ge = pl.pallas_call(
            body,
            grid=(N // bi, M // bj, n_mtiles),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((bi, bj), lambda i, j, jm: (i, j)),
                pl.BlockSpec((bi, bj), lambda i, j, jm: (i, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((N, M), acc),
                jax.ShapeDtypeStruct((N, M), acc),
            ],
            interpret=interpret,
            name=kernel_name(spec),
            **_compiler_params(spec, 3, interpret),
        )(*operands)
        return le, ge

    return rect_pallas


def _emit_rect_i32_stats(spec: CompareSpec):
    bi, bj, bm = spec.bi, spec.bj, spec.bm

    def kernel(a_ref, b_ref, le_ref, ge_ref, asums_ref):
        j = pl.program_id(1)       # column-tile index
        jm = pl.program_id(2)      # m-tile index (innermost -> revisits)
        # wrap-subtraction dominance: this is the rim engine promoted
        # near-wrap rows ride, so it must stay correct across the wrap
        _pairwise_flags(jm, a_ref, b_ref[...], le_ref, ge_ref, jnp.int32)
        sa = _row_sum(a_ref[...])

        # row sums: the (i, 0) block stays live for the whole i-row of
        # the grid, so add each m-tile exactly once (j == 0 stripe)
        @pl.when(jnp.logical_and(j == 0, jm == 0))
        def _init_sums():
            asums_ref[...] = sa

        @pl.when(jnp.logical_and(j == 0, jm > 0))
        def _acc_sums():
            asums_ref[...] = asums_ref[...] + sa

    @functools.partial(jax.jit, static_argnames=("m_true", "interpret"))
    def rect_i32_pallas(rows, cols, col_sums, *, m_true=None,
                        interpret=False):
        """Tiled all-pairs int32 compare with in-kernel row sums; Eq. 3
        fp(row -> col) is the outer product of the sums."""
        validate(spec, _backend(interpret))
        N, m = rows.shape
        M, mc = cols.shape
        assert m == mc and col_sums.shape == (1, M)
        assert N % bi == 0 and M % bj == 0 and m % bm == 0, \
            (N, M, m, bi, bj, bm)
        le, ge, row_sums = pl.pallas_call(
            kernel,
            grid=(N // bi, M // bj, m // bm),
            in_specs=[
                pl.BlockSpec((bi, bm), lambda i, j, jm: (i, jm)),
                pl.BlockSpec((bj, bm), lambda i, j, jm: (j, jm)),
            ],
            out_specs=[
                pl.BlockSpec((bi, bj), lambda i, j, jm: (i, j)),
                pl.BlockSpec((bi, bj), lambda i, j, jm: (i, j)),
                pl.BlockSpec((bi, 1), lambda i, j, jm: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((N, M), jnp.int32),
                jax.ShapeDtypeStruct((N, M), jnp.int32),
                jax.ShapeDtypeStruct((N, 1), jnp.float32),
            ],
            interpret=interpret,
            name=kernel_name(spec),
            **_compiler_params(spec, 3, interpret),
        )(rows, cols)
        fp = eq3_fp(row_sums, col_sums, m_true if m_true else m)
        return le, ge, row_sums, fp

    return rect_i32_pallas


def _emit_mxu(spec: CompareSpec):
    bi, bj, bm, n_thr = spec.bi, spec.bj, spec.bm, spec.n_thresholds

    def kernel(a_ref, b_ref, abase_ref, bbase_ref, viol_ref, *, lo, m_true):
        jm = pl.program_id(2)
        # shift residuals to window-relative logical values in [0, T]
        av = a_ref[...].astype(jnp.int32) + (abase_ref[...] - lo)
        bv = b_ref[...].astype(jnp.int32) + (bbase_ref[...] - lo)
        # padded lanes must contribute zero violations either way
        col = jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1) + jm * bm
        av = jnp.where(col < m_true, av, -1)           # a >= t never
        bv = jnp.where(col < m_true, bv, n_thr + 1)    # b <  t never

        # sum_m relu(a - b) == #{(m, t): b_jm < t <= a_im}: one MXU
        # contraction per threshold t = 1 .. T.  Counts are integers
        # < 2^24, so the f32 sum is exact in any order.
        def threshold(t, v):
            enc_a = jnp.where(av >= t, 1.0, 0.0)       # [bi, bm] f32
            enc_b = jnp.where(bv < t, 1.0, 0.0)        # [bj, bm] f32
            return v + jax.lax.dot_general(
                enc_a, enc_b, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # [bi, bj]

        v = jax.lax.fori_loop(1, n_thr + 1, threshold,
                              jnp.zeros((bi, bj), jnp.float32))

        @pl.when(jm == 0)
        def _init():
            viol_ref[...] = v

        @pl.when(jm > 0)
        def _acc():
            viol_ref[...] = viol_ref[...] + v

    @functools.partial(jax.jit, static_argnames=("lo", "m_true", "interpret"))
    def mxu_pallas(rows, cols, row_base, col_base, *, lo, m_true=None,
                   interpret=False):
        """MXU dominance reduction: violation counts via dot_general.

        Returns viol f32 [N, M] with ``viol[i, j] == sum_m relu(a_im -
        b_jm)`` exactly (counts <= m * T << 2^24).  ``le = viol == 0``;
        the caller derives ``ge`` from the rank-1 identity with row/col
        sums.  Requires every logical value in [lo, lo + T]."""
        validate(spec, _backend(interpret))
        N, m = rows.shape
        M, mc = cols.shape
        assert m == mc and N % bi == 0 and M % bj == 0 and m % bm == 0
        # violation counts accumulate in f32: keep them exactly
        # representable
        assert (m_true if m_true else m) * n_thr < 2**24, \
            (m_true, n_thr, "f32 exactness bound exceeded")
        body = functools.partial(kernel, lo=lo,
                                 m_true=m_true if m_true else m)
        viol = pl.pallas_call(
            body,
            grid=(N // bi, M // bj, m // bm),
            in_specs=[
                pl.BlockSpec((bi, bm), lambda i, j, jm: (i, jm)),
                pl.BlockSpec((bj, bm), lambda i, j, jm: (j, jm)),
                pl.BlockSpec((bi, 1), lambda i, j, jm: (i, 0)),
                pl.BlockSpec((bj, 1), lambda i, j, jm: (j, 0)),
            ],
            out_specs=pl.BlockSpec((bi, bj), lambda i, j, jm: (i, j)),
            out_shape=jax.ShapeDtypeStruct((N, M), jnp.float32),
            interpret=interpret,
            name=kernel_name(spec),
            **_compiler_params(spec, 3, interpret),
        )(rows, cols, row_base, col_base)
        return viol

    return mxu_pallas


def _emit_one_vs_many(spec: CompareSpec):
    bn, bm, packed = spec.bi, spec.bm, spec.pack == "u8"
    acc = spec.acc_dtype

    def kernel(q_ref, p_ref, *rest, m, n_mtiles):
        if packed:
            pbase_ref, flags_ref, sums_ref, pbase_col, *scratch = rest
        else:
            flags_ref, sums_ref, *scratch = rest
        j = pl.program_id(1)
        q = q_ref[...]                                 # [1, bm] int32
        if packed:
            @pl.when(j == 0)
            def _base_column():
                pbase_col[...] = pbase_ref[...].T      # [1, bn] -> [bn, 1]

            # widen the u8 peer tile in VMEM; HBM reads stay 1 B/cell
            p = p_ref[...].astype(jnp.int32) + pbase_col[...]
            col = jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1) + j * bm
            p = jnp.where(col < m, p, 0)               # neutral pad lanes
        else:
            p = p_ref[...]                             # [bn, bm] int32
        _rows_step(j, n_mtiles, scratch, flags_ref, sums_ref,
                   _one_vs_many_flags(q, p, acc), _row_sum(q), _row_sum(p))

    @functools.partial(jax.jit, static_argnames=("m_true", "interpret"))
    def one_vs_many_pallas(q, peers, base=None, *, m_true=None,
                           interpret=False):
        """One-vs-many classify of ``q`` ([1, m]) against ``peers`` ([N,
        m]; packed: plus ``base``, a [1, N] int32 row).  Returns flags
        ([2, N]: q ≼ p, p ≼ q), total sums ([2, N] f32: sum_q, sum_p)
        and Eq. 3 fp ([2, N]: q before p, p before q), all dense rows."""
        validate(spec, _backend(interpret))
        N, m = peers.shape
        assert q.shape == (1, m) and m % bm == 0 and N % bn == 0
        m_true = m_true if m_true else m
        in_specs = [
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),
            pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        ]
        operands = [q, peers]
        scratch = _rows_scratch(bn, acc)
        if packed:
            assert base.shape == (1, N), (base.shape, N)
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, i)))
            operands.append(base)
            scratch.insert(0, pltpu.VMEM((bn, 1), jnp.int32))
        flags, sums = pl.pallas_call(
            functools.partial(kernel, m=m_true, n_mtiles=m // bm),
            grid=(N // bn, m // bm),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((2, bn), lambda i, j: (0, i)),
                pl.BlockSpec((2, bn), lambda i, j: (0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((2, N), acc),
                jax.ShapeDtypeStruct((2, N), jnp.float32),
            ],
            scratch_shapes=scratch,
            interpret=interpret,
            name=kernel_name(spec),
            **_compiler_params(spec, 2, interpret),
        )(*operands)
        return flags, sums, _eq3_pairs(sums, m_true, axis=0)

    return one_vs_many_pallas


def _emit_hybrid(spec: CompareSpec):
    bn, bm = spec.bi, spec.bm
    acc = spec.acc_dtype

    def kernel(vloc_ref, q_ref, meta_ref, hsum_ref, p_ref, pbase_ref,
               flags_ref, sums_ref, pbase_col, *scratch, m, nh_tiles,
               n_mtiles):
        i = pl.program_id(0)
        j = pl.program_id(1)
        _, sq_acc, _ = scratch                         # _rows_scratch
        q = q_ref[...]                                 # [1, bm] int32
        sq = _row_sum(q)

        @pl.when(i < nh_tiles)
        def _hot():
            # Exact chain-prefix verdicts.  A hot row is the pair (v =
            # minting-chain prefix length, n_private = events past the
            # prefix); against the local chain at version V the order
            # is an integer compare — no bloom cells, no Eq. 3 exposure.
            # Nothing here depends on the m-tile but sum_q, so the rows
            # are written once, at the last m-tile.
            _accumulate_sq(j, sq_acc, sq)

            @pl.when(j == n_mtiles - 1)
            def _store():
                V = vloc_ref[0, 0]
                v = meta_ref[0:1, :]                   # [1, bn] rows
                npriv = meta_ref[1:2, :]
                flags_ref[...] = _two_rows(
                    _flag(V <= v, acc),                # local ≼ peer
                    _flag(jnp.logical_and(v <= V, npriv == 0),
                          acc))                        # peer ≼ local
                # sum_q accumulates per m-tile for hot rows too, so the
                # caller's sum_q (read off row 0) matches the tail
                # engines bit for bit; sum_p of a hot row is its
                # precomputed shadow sum
                sums_ref[...] = _two_rows(sq_acc[...], hsum_ref[...])

        @pl.when(i >= nh_tiles)
        def _tail():
            # the UNMODIFIED packed one-vs-many math: tail verdicts /
            # sums stay bit-identical to the flat slab
            @pl.when(j == 0)
            def _base_column():
                pbase_col[...] = pbase_ref[...].T      # [1, bn] -> [bn, 1]

            p = p_ref[...].astype(jnp.int32) + pbase_col[...]
            col = jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1) + j * bm
            p = jnp.where(col < m, p, 0)               # neutral pad lanes
            _rows_step(j, n_mtiles, scratch, flags_ref, sums_ref,
                       _one_vs_many_flags(q, p, acc), sq, _row_sum(p))

    @functools.partial(jax.jit, static_argnames=("m_true", "interpret"))
    def hybrid_pallas(q, v_local, hot_meta, hot_sums, tail, tail_base, *,
                      m_true=None, interpret=False):
        """One query vs [exact hot rows ++ packed tail] in one sweep.

        ``hot_meta`` is [2, H] int32 (rows v, n_private), ``hot_sums``
        [1, H] f32 and ``tail_base`` [1, T] int32.  Outputs are [2, H+T]
        dense rows as in ``one_vs_many``, stacked hot-first: columns [0,
        H) are the hot set (exact flags, fp ≡ 0.0), [H, H+T) the packed
        tail (flags/sums/fp bit-identical to the one_vs_many packed
        engine)."""
        validate(spec, _backend(interpret))
        H = hot_meta.shape[1]
        T, m = tail.shape
        assert q.shape == (1, m) and m % bm == 0, (q.shape, m, bm)
        assert H % bn == 0 and T % bn == 0 and H > 0 and T > 0, (H, T, bn)
        assert hot_meta.shape == (2, H) and hot_sums.shape == (1, H)
        assert tail_base.shape == (1, T) and v_local.shape == (1, 1)
        nh_tiles = H // bn
        m_true = m_true if m_true else m
        body = functools.partial(kernel, m=m_true, nh_tiles=nh_tiles,
                                 n_mtiles=m // bm)
        # Hot tiles clamp the tail index maps to block 0 (and vice
        # versa): every grid step fetches valid blocks, and only the
        # branch of its own side runs.
        in_specs = [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),
            pl.BlockSpec((2, bn),
                         lambda i, j: (0, jnp.minimum(i, nh_tiles - 1))),
            pl.BlockSpec((1, bn),
                         lambda i, j: (0, jnp.minimum(i, nh_tiles - 1))),
            pl.BlockSpec((bn, bm),
                         lambda i, j: (jnp.maximum(i - nh_tiles, 0), j)),
            pl.BlockSpec((1, bn),
                         lambda i, j: (0, jnp.maximum(i - nh_tiles, 0))),
        ]
        flags, sums = pl.pallas_call(
            body,
            grid=(nh_tiles + T // bn, m // bm),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((2, bn), lambda i, j: (0, i)),
                pl.BlockSpec((2, bn), lambda i, j: (0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((2, H + T), acc),
                jax.ShapeDtypeStruct((2, H + T), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((bn, 1), jnp.int32),
                            *_rows_scratch(bn, acc)],
            interpret=interpret,
            name=kernel_name(spec),
            **_compiler_params(spec, 2, interpret),
        )(v_local, q, hot_meta, hot_sums, tail, tail_base)
        hot = jnp.arange(H + T)[None, :] < H
        fp = jnp.where(hot, 0.0, _eq3_pairs(sums, m_true, axis=0))
        return flags, sums, fp

    return hybrid_pallas


@functools.lru_cache(maxsize=None)
def emit(spec: CompareSpec):
    """Validated, jitted wrapper for one point in the design space.

    Cached per spec, so repeated emission of the same instance reuses
    the same jitted callable (and its compiled executables)."""
    validate(spec)
    if spec.topology == "tri":
        return _emit_tri(spec)
    if spec.topology == "rect":
        if spec.pack == "i32":
            return _emit_rect_i32_stats(spec)
        return _emit_rect_u8(spec)
    if spec.topology == "mxu":
        return _emit_mxu(spec)
    if spec.topology == "hybrid":
        return _emit_hybrid(spec)
    return _emit_one_vs_many(spec)
