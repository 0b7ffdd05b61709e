"""Bit-identity pins for the template-emitted compare-kernel family.

``kernels.template`` + ``kernels.generate`` replaced the hand-rolled
engine bodies that used to live in ``kernels.bloom_matrix``.  The
contract of that refactor is exact: every emitted instance must produce
byte-for-byte the outputs (flags, sums, Eq. 3 fp bits, dtypes) of the
kernel it replaced.  This module carries VERBATIM copies of the deleted
pre-refactor kernels (prefixed ``_legacy_``) and pins each instance
against them, so any drift in the template — reordered ops, a changed
accumulate dtype, a different Eq. 3 expression — fails here even if the
result stays semantically "correct".

Also pinned: the generator's refusal of malformed specs and of knob
combinations whose analytic VMEM estimate exceeds the backend budget,
and (property tests) end-to-end agreement of every engine x pack mode
with the broadcast reference ``comparability_matrix``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import causal
from repro.core import clock as bc
from repro.kernels import ops, pack
from repro.kernels.generate import (
    ENGINE_SPECS,
    bloom_matrix_mxu_pallas,
    bloom_matrix_packed_pallas,
    bloom_matrix_pallas,
    bloom_matrix_tri_pallas,
    bloom_one_vs_many_packed_pallas,
    bloom_one_vs_many_pallas,
)
from repro.kernels.ref import eq3_fp
from repro.kernels.template import (
    VMEM_BUDGET,
    CompareSpec,
    emit,
    validate,
    vmem_estimate,
)

RNG = np.random.default_rng(77)


# ---------------------------------------------------------------------------
# VERBATIM pre-refactor kernels (deleted from bloom_matrix.py in PR 7).
# Do not "fix" or modernize these — they are the reference the template
# is pinned against.
# ---------------------------------------------------------------------------

def _legacy_one_vs_many_kernel(
    q_ref, p_ref,
    flags_ref, sums_ref, fp_ref,
    *, n_mtiles: int, m: int,
):
    j = pl.program_id(1)
    q = q_ref[...]
    p = p_ref[...]

    le = jnp.all(q <= p, axis=1, keepdims=True)
    ge = jnp.all(q >= p, axis=1, keepdims=True)
    sp = jnp.sum(p, axis=1, keepdims=True).astype(jnp.float32)
    sq = jnp.broadcast_to(
        jnp.sum(q, axis=1, keepdims=True).astype(jnp.float32), sp.shape)

    @pl.when(j == 0)
    def _init():
        flags_ref[...] = jnp.concatenate([le, ge], axis=1).astype(jnp.int32)
        sums_ref[...] = jnp.concatenate([sq, sp], axis=1)

    @pl.when(j > 0)
    def _acc():
        cur = jnp.concatenate([le, ge], axis=1).astype(jnp.int32)
        flags_ref[...] = flags_ref[...] & cur
        sums_ref[...] = sums_ref[...] + jnp.concatenate([sq, sp], axis=1)

    @pl.when(j == n_mtiles - 1)
    def _finalize():
        s = sums_ref[...]
        log_q = jnp.log1p(-1.0 / m)
        inner_p = jnp.clip(-jnp.expm1(s[:, 1:2] * log_q), 1e-30, 1.0)
        inner_q = jnp.clip(-jnp.expm1(s[:, 0:1] * log_q), 1e-30, 1.0)
        fp_qp = jnp.exp(s[:, 0:1] * jnp.log(inner_p))
        fp_pq = jnp.exp(s[:, 1:2] * jnp.log(inner_q))
        fp_ref[...] = jnp.concatenate([fp_qp, fp_pq], axis=1)


@functools.partial(jax.jit, static_argnames=("bn", "bm", "m_true", "interpret"))
def _legacy_one_vs_many_pallas(q, peers, *, bn=8, bm=512, m_true=None,
                               interpret=False):
    N, m = peers.shape
    assert q.shape == (1, m) and m % bm == 0 and N % bn == 0
    n_mtiles = m // bm
    kernel = functools.partial(
        _legacy_one_vs_many_kernel, n_mtiles=n_mtiles,
        m=m_true if m_true else m)
    return pl.pallas_call(
        kernel,
        grid=(N // bn, n_mtiles),
        in_specs=[
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),
            pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 2), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 2), jnp.int32),
            jax.ShapeDtypeStruct((N, 2), jnp.float32),
            jax.ShapeDtypeStruct((N, 2), jnp.float32),
        ],
        interpret=interpret,
    )(q, peers)


def _legacy_matrix_kernel(
    a_ref, b_ref, bsums_ref,
    le_ref, ge_ref, asums_ref, fp_ref,
    *, n_mtiles: int, m: int,
):
    j = pl.program_id(1)
    jm = pl.program_id(2)
    a = a_ref[...]
    b = b_ref[...]

    le = jnp.all(a[:, None, :] <= b[None, :, :], axis=2)
    ge = jnp.all(a[:, None, :] >= b[None, :, :], axis=2)
    sa = jnp.sum(a, axis=1, keepdims=True).astype(jnp.float32)

    @pl.when(jnp.logical_and(j == 0, jm == 0))
    def _init_sums():
        asums_ref[...] = sa

    @pl.when(jnp.logical_and(j == 0, jm > 0))
    def _acc_sums():
        asums_ref[...] = asums_ref[...] + sa

    @pl.when(jm == 0)
    def _init_flags():
        le_ref[...] = le.astype(jnp.int32)
        ge_ref[...] = ge.astype(jnp.int32)

    @pl.when(jm > 0)
    def _acc_flags():
        le_ref[...] = le_ref[...] & le.astype(jnp.int32)
        ge_ref[...] = ge_ref[...] & ge.astype(jnp.int32)

    @pl.when(jm == n_mtiles - 1)
    def _finalize():
        sa_tot = asums_ref[...]
        sb_tot = bsums_ref[...]
        log_q = jnp.log1p(-1.0 / m)
        inner_b = jnp.clip(-jnp.expm1(sb_tot * log_q), 1e-30, 1.0)
        fp_ref[...] = jnp.exp(sa_tot * jnp.log(inner_b))


@functools.partial(
    jax.jit, static_argnames=("bi", "bj", "bm", "m_true", "interpret"))
def _legacy_matrix_pallas(rows, cols, col_sums, *, bi=8, bj=128, bm=512,
                          m_true=None, interpret=False):
    N, m = rows.shape
    M, mc = cols.shape
    assert m == mc and col_sums.shape == (1, M)
    assert N % bi == 0 and M % bj == 0 and m % bm == 0
    n_mtiles = m // bm
    kernel = functools.partial(
        _legacy_matrix_kernel, n_mtiles=n_mtiles, m=m_true if m_true else m)
    return pl.pallas_call(
        kernel,
        grid=(N // bi, M // bj, n_mtiles),
        in_specs=[
            pl.BlockSpec((bi, bm), lambda i, j, jm: (i, jm)),
            pl.BlockSpec((bj, bm), lambda i, j, jm: (j, jm)),
            pl.BlockSpec((1, bj), lambda i, j, jm: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bi, bj), lambda i, j, jm: (i, j)),
            pl.BlockSpec((bi, bj), lambda i, j, jm: (i, j)),
            pl.BlockSpec((bi, 1), lambda i, j, jm: (i, 0)),
            pl.BlockSpec((bi, bj), lambda i, j, jm: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, M), jnp.int32),
            jax.ShapeDtypeStruct((N, M), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, M), jnp.float32),
        ],
        interpret=interpret,
    )(rows, cols, col_sums)


def _legacy_pair_flags_minmax(a_ref, b_ref, abase_ref, bbase_ref,
                              *, with_base, m_true, bm, jm):
    a = a_ref[...]
    b = b_ref[...]
    d = a.astype(jnp.int16)[:, None, :] - b.astype(jnp.int16)[None, :, :]
    if with_base:
        delta = jnp.clip(abase_ref[...] - bbase_ref[...].T, -256, 256)
        d = d + delta[:, :, None].astype(jnp.int16)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, bm), 2) + jm * bm
        d = jnp.where(col < m_true, d, 0)
    le = (jnp.max(d, axis=2) <= 0).astype(jnp.int8)
    ge = (jnp.min(d, axis=2) >= 0).astype(jnp.int8)
    return le, ge


def _legacy_flags_kernel_step(refs, *, jm, with_base, m_true, bm):
    if with_base:
        a_ref, b_ref, abase_ref, bbase_ref, le_ref, ge_ref = refs
    else:
        a_ref, b_ref, le_ref, ge_ref = refs
        abase_ref = bbase_ref = None
    le, ge = _legacy_pair_flags_minmax(a_ref, b_ref, abase_ref, bbase_ref,
                                       with_base=with_base, m_true=m_true,
                                       bm=bm, jm=jm)

    @pl.when(jm == 0)
    def _init():
        le_ref[...] = le
        ge_ref[...] = ge

    @pl.when(jm > 0)
    def _acc():
        le_ref[...] = le_ref[...] & le
        ge_ref[...] = ge_ref[...] & ge


def _legacy_tri_kernel(ti_ref, tj_ref, *refs, n_mtiles, with_base,
                       m_true, bm):
    _legacy_flags_kernel_step(refs, jm=pl.program_id(1),
                              with_base=with_base, m_true=m_true, bm=bm)


@functools.partial(
    jax.jit, static_argnames=("bi", "bm", "m_true", "with_base", "interpret"))
def _legacy_tri_pallas(cells, base, *, bi=128, bm=512, m_true=None,
                       with_base=False, interpret=False):
    N, m = cells.shape
    assert N % bi == 0 and m % bm == 0, (N, m, bi, bm)
    k = N // bi
    tri = [(i, j) for i in range(k) for j in range(i, k)]
    ti = jnp.asarray([i for i, _ in tri], jnp.int32)
    tj = jnp.asarray([j for _, j in tri], jnp.int32)
    n_mtiles = m // bm
    kernel = functools.partial(
        _legacy_tri_kernel, n_mtiles=n_mtiles, with_base=with_base,
        m_true=m_true if m_true else m, bm=bm)
    in_specs = [
        pl.BlockSpec((bi, bm), lambda t, jm, ti, tj: (ti[t], jm)),
        pl.BlockSpec((bi, bm), lambda t, jm, ti, tj: (tj[t], jm)),
    ]
    operands = [cells, cells]
    if with_base:
        in_specs += [
            pl.BlockSpec((bi, 1), lambda t, jm, ti, tj: (ti[t], 0)),
            pl.BlockSpec((bi, 1), lambda t, jm, ti, tj: (tj[t], 0)),
        ]
        operands += [base, base]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(len(tri), n_mtiles),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bi, bi), lambda t, jm, ti, tj: (ti[t], tj[t])),
            pl.BlockSpec((bi, bi), lambda t, jm, ti, tj: (ti[t], tj[t])),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((N, N), jnp.int8),
            jax.ShapeDtypeStruct((N, N), jnp.int8),
        ],
        interpret=interpret,
    )(ti, tj, *operands)


def _legacy_packed_kernel(*refs, n_mtiles, with_base, m_true, bm):
    _legacy_flags_kernel_step(refs, jm=pl.program_id(2),
                              with_base=with_base, m_true=m_true, bm=bm)


@functools.partial(
    jax.jit,
    static_argnames=("bi", "bj", "bm", "m_true", "with_base", "interpret"))
def _legacy_packed_pallas(rows, cols, row_base, col_base, *, bi=128, bj=128,
                          bm=512, m_true=None, with_base=False,
                          interpret=False):
    N, m = rows.shape
    M, mc = cols.shape
    assert m == mc and N % bi == 0 and M % bj == 0 and m % bm == 0
    n_mtiles = m // bm
    kernel = functools.partial(
        _legacy_packed_kernel, n_mtiles=n_mtiles, with_base=with_base,
        m_true=m_true if m_true else m, bm=bm)
    in_specs = [
        pl.BlockSpec((bi, bm), lambda i, j, jm: (i, jm)),
        pl.BlockSpec((bj, bm), lambda i, j, jm: (j, jm)),
    ]
    operands = [rows, cols]
    if with_base:
        in_specs += [
            pl.BlockSpec((bi, 1), lambda i, j, jm: (i, 0)),
            pl.BlockSpec((bj, 1), lambda i, j, jm: (j, 0)),
        ]
        operands += [row_base, col_base]
    return pl.pallas_call(
        kernel,
        grid=(N // bi, M // bj, n_mtiles),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bi, bj), lambda i, j, jm: (i, j)),
            pl.BlockSpec((bi, bj), lambda i, j, jm: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, M), jnp.int8),
            jax.ShapeDtypeStruct((N, M), jnp.int8),
        ],
        interpret=interpret,
    )(*operands)


def _legacy_mxu_kernel(a_ref, b_ref, abase_ref, bbase_ref, viol_ref,
                       *, n_mtiles, n_thresholds, lo, m_true, bm):
    jm = pl.program_id(2)
    av = a_ref[...].astype(jnp.int32) + (abase_ref[...] - lo)
    bv = b_ref[...].astype(jnp.int32) + (bbase_ref[...] - lo)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1) + jm * bm
    av = jnp.where(col < m_true, av, -1)
    bv = jnp.where(col < m_true, bv, n_thresholds + 1)
    thr = jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, n_thresholds), 2) + 1
    bi_, bj_ = av.shape[0], bv.shape[0]
    enc_a = (av[:, :, None] >= thr).reshape(bi_, -1).astype(jnp.float32)
    enc_b = (bv[:, :, None] < thr).reshape(bj_, -1).astype(jnp.float32)
    v = jax.lax.dot_general(
        enc_a, enc_b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(jm == 0)
    def _init():
        viol_ref[...] = v

    @pl.when(jm > 0)
    def _acc():
        viol_ref[...] = viol_ref[...] + v


@functools.partial(
    jax.jit,
    static_argnames=("bi", "bj", "bm", "n_thresholds", "lo", "m_true",
                     "interpret"))
def _legacy_mxu_pallas(rows, cols, row_base, col_base, *, n_thresholds, lo,
                       bi=128, bj=128, bm=128, m_true=None, interpret=False):
    N, m = rows.shape
    M, mc = cols.shape
    assert m == mc and N % bi == 0 and M % bj == 0 and m % bm == 0
    assert (m_true if m_true else m) * n_thresholds < 2**24
    n_mtiles = m // bm
    kernel = functools.partial(
        _legacy_mxu_kernel, n_mtiles=n_mtiles,
        n_thresholds=n_thresholds, lo=lo,
        m_true=m_true if m_true else m, bm=bm)
    return pl.pallas_call(
        kernel,
        grid=(N // bi, M // bj, n_mtiles),
        in_specs=[
            pl.BlockSpec((bi, bm), lambda i, j, jm: (i, jm)),
            pl.BlockSpec((bj, bm), lambda i, j, jm: (j, jm)),
            pl.BlockSpec((bi, 1), lambda i, j, jm: (i, 0)),
            pl.BlockSpec((bj, 1), lambda i, j, jm: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, jm: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, M), jnp.float32),
        interpret=interpret,
    )(rows, cols, row_base, col_base)


def _legacy_one_vs_many_packed_kernel(
    q_ref, p_ref, pbase_ref,
    flags_ref, sums_ref, fp_ref,
    *, n_mtiles: int, m: int, bm: int,
):
    j = pl.program_id(1)
    q = q_ref[...]
    p = p_ref[...].astype(jnp.int32) + pbase_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1) + j * bm
    p = jnp.where(col < m, p, 0)

    le = jnp.all(q <= p, axis=1, keepdims=True)
    ge = jnp.all(q >= p, axis=1, keepdims=True)
    sp = jnp.sum(p, axis=1, keepdims=True).astype(jnp.float32)
    sq = jnp.broadcast_to(
        jnp.sum(q, axis=1, keepdims=True).astype(jnp.float32), sp.shape)

    @pl.when(j == 0)
    def _init():
        flags_ref[...] = jnp.concatenate([le, ge], axis=1).astype(jnp.int32)
        sums_ref[...] = jnp.concatenate([sq, sp], axis=1)

    @pl.when(j > 0)
    def _acc():
        cur = jnp.concatenate([le, ge], axis=1).astype(jnp.int32)
        flags_ref[...] = flags_ref[...] & cur
        sums_ref[...] = sums_ref[...] + jnp.concatenate([sq, sp], axis=1)

    @pl.when(j == n_mtiles - 1)
    def _finalize():
        s = sums_ref[...]
        log_q = jnp.log1p(-1.0 / m)
        inner_p = jnp.clip(-jnp.expm1(s[:, 1:2] * log_q), 1e-30, 1.0)
        inner_q = jnp.clip(-jnp.expm1(s[:, 0:1] * log_q), 1e-30, 1.0)
        fp_qp = jnp.exp(s[:, 0:1] * jnp.log(inner_p))
        fp_pq = jnp.exp(s[:, 1:2] * jnp.log(inner_q))
        fp_ref[...] = jnp.concatenate([fp_qp, fp_pq], axis=1)


@functools.partial(jax.jit, static_argnames=("bn", "bm", "m_true", "interpret"))
def _legacy_one_vs_many_packed_pallas(q, peers, base, *, bn=8, bm=512,
                                      m_true=None, interpret=False):
    N, m = peers.shape
    assert q.shape == (1, m) and m % bm == 0 and N % bn == 0
    n_mtiles = m // bm
    kernel = functools.partial(
        _legacy_one_vs_many_packed_kernel, n_mtiles=n_mtiles,
        m=m_true if m_true else m, bm=bm)
    return pl.pallas_call(
        kernel,
        grid=(N // bn, n_mtiles),
        in_specs=[
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),
            pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 2), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 2), jnp.int32),
            jax.ShapeDtypeStruct((N, 2), jnp.float32),
            jax.ShapeDtypeStruct((N, 2), jnp.float32),
        ],
        interpret=interpret,
    )(q, peers, base)


# ---------------------------------------------------------------------------
# shared random inputs
# ---------------------------------------------------------------------------

N, M, m = 16, 24, 256
BI, BJ, BM = 8, 8, 128


def _packed_inputs():
    rows = jnp.asarray(RNG.integers(0, 200, (N, m)), jnp.uint8)
    cols = jnp.asarray(RNG.integers(0, 200, (M, m)), jnp.uint8)
    rb = jnp.asarray(RNG.integers(0, 5, (N, 1)), jnp.int32)
    cb = jnp.asarray(RNG.integers(0, 5, (M, 1)), jnp.int32)
    return rows, cols, rb, cb


def _assert_bit_identical(got, want, label):
    got = got if isinstance(got, (tuple, list)) else (got,)
    want = want if isinstance(want, (tuple, list)) else (want,)
    assert len(got) == len(want)
    for k, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype, (label, k, g.dtype, w.dtype)
        assert g.shape == w.shape, (label, k, g.shape, w.shape)
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=f"{label} output {k}")


# ---------------------------------------------------------------------------
# the pins: emitted instance == verbatim legacy kernel, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("with_base", [False, True])
def test_template_rect_pins_legacy(with_base):
    rows, cols, rb, cb = _packed_inputs()
    kw = dict(bi=BI, bj=BJ, bm=BM, m_true=m - 3, with_base=with_base,
              interpret=True)
    _assert_bit_identical(
        bloom_matrix_packed_pallas(rows, cols, rb, cb, **kw),
        _legacy_packed_pallas(rows, cols, rb, cb, **kw),
        f"rect(with_base={with_base})")


@pytest.mark.parametrize("with_base", [False, True])
def test_template_tri_pins_legacy(with_base):
    rows, _, rb, _ = _packed_inputs()
    kw = dict(bi=BI, bm=BM, m_true=m - 3, with_base=with_base,
              interpret=True)
    _assert_bit_identical(
        bloom_matrix_tri_pallas(rows, rb, **kw),
        _legacy_tri_pallas(rows, rb, **kw),
        f"tri(with_base={with_base})")


def test_template_mxu_pins_legacy():
    _, _, rb, cb = _packed_inputs()
    rows = jnp.asarray(RNG.integers(0, 30, (N, m)), jnp.uint8)
    cols = jnp.asarray(RNG.integers(0, 30, (M, m)), jnp.uint8)
    kw = dict(n_thresholds=40, lo=0, bi=BI, bj=BJ, bm=BM, m_true=m - 3,
              interpret=True)
    _assert_bit_identical(
        bloom_matrix_mxu_pallas(rows, cols, rb, cb, **kw),
        _legacy_mxu_pallas(rows, cols, rb, cb, **kw),
        "mxu")


def test_template_i32_stats_pins_legacy():
    rows = jnp.asarray(RNG.integers(0, 9, (N, m)), jnp.int32)
    cols = jnp.asarray(RNG.integers(0, 9, (M, m)), jnp.int32)
    col_sums = jnp.sum(cols, axis=1, dtype=jnp.float32)[None, :]
    kw = dict(bi=BI, bj=BJ, bm=BM, m_true=m, interpret=True)
    _assert_bit_identical(
        bloom_matrix_pallas(rows, cols, col_sums, **kw),
        _legacy_matrix_pallas(rows, cols, col_sums, **kw),
        "i32-stats")


def _transposed(outs):
    """The legacy kernels' [N, 2] outputs as the [2, N] dense rows the
    emitted one-vs-many instances return."""
    return [x.T for x in outs]


def test_template_one_vs_many_i32_pins_legacy():
    peers = jnp.asarray(RNG.integers(0, 9, (N, m)), jnp.int32)
    q = jnp.asarray(RNG.integers(0, 9, (1, m)), jnp.int32)
    kw = dict(bn=8, bm=BM, m_true=m, interpret=True)
    _assert_bit_identical(
        bloom_one_vs_many_pallas(q, peers, **kw),
        _transposed(_legacy_one_vs_many_pallas(q, peers, **kw)),
        "one_vs_many-i32")


def test_template_one_vs_many_packed_pins_legacy():
    rows, _, rb, _ = _packed_inputs()
    q = jnp.asarray(RNG.integers(0, 9, (1, m)), jnp.int32)
    kw = dict(bn=8, bm=BM, m_true=m - 3, interpret=True)
    _assert_bit_identical(
        bloom_one_vs_many_packed_pallas(q, rows, rb.reshape(1, -1), **kw),
        _transposed(_legacy_one_vs_many_packed_pallas(q, rows, rb, **kw)),
        "one_vs_many-packed")


# ---------------------------------------------------------------------------
# one-vs-many and hybrid dense-row results vs plain references, bit for
# bit, through the ops runners (ragged m, rows and hot set; int32 wrap)
# ---------------------------------------------------------------------------

INT32_MAX = 2**31 - 1


def _wrap32(x):
    """int64 values wrapped onto int32, as the kernels' int32 adds do."""
    return ((np.asarray(x, np.int64) + 2**31) % 2**32 - 2**31).astype(
        np.int32)


def _chunked_sums(rows, bm):
    """Row sums as the kernels define them: an int32 (wrapping) sum per
    bm-cell chunk, converted to f32 and added in chunk order."""
    acc = None
    for c in range(0, rows.shape[1], bm):
        s = _wrap32(rows[:, c:c + bm].astype(np.int64).sum(axis=1))
        s = s.astype(np.float32)
        acc = s if acc is None else acc + s
    return acc


def _ovm_reference(q, logical, bm):
    """Plain one-vs-many reference: int32 wrap-subtraction dominance,
    chunked sums, Eq. 3 by the reference expression."""
    d = _wrap32(logical.astype(np.int64) - q.astype(np.int64))
    sum_q = _chunked_sums(q[None, :], bm)[0]
    sum_p = _chunked_sums(logical, bm)
    sq = np.full_like(sum_p, sum_q)
    m = q.shape[0]
    return {
        "q_le_p": d.min(axis=1) >= 0,
        "p_le_q": d.max(axis=1) <= 0,
        "sum_q": sum_q,
        "sum_p": sum_p,
        "fp_q_before_p": np.asarray(eq3_fp(sq, sum_p, m)),
        "fp_p_before_q": np.asarray(eq3_fp(sum_p, sq, m)),
    }


def _ordered_rows(rng, n, m, lo):
    """(q, u8, base): a third of the rows at or above q, a third at or
    below it, the rest drawn at random, every value ``lo`` plus a byte."""
    qu = rng.integers(2, 120, m)
    u8 = rng.integers(0, 124, (n, m))
    u8[0::3] = qu + rng.integers(0, 3, u8[0::3].shape)
    u8[1::3] = qu - rng.integers(0, 3, u8[1::3].shape)
    base = np.full(n, lo, np.int64)
    base[2::3] += rng.integers(0, 3, base[2::3].shape)
    return (_wrap32(qu + lo), u8.astype(np.uint8), _wrap32(base))


#: (rows, m, bn, bm, base of every value): ragged m leaves a partial
#: last m-tile, ragged rows a padded row block, "wrap" puts the logical
#: cells across the int32 wrap point
DENSE_CASES = {
    "m_ragged": (32, 300, 8, 128, 1000),
    "rows_ragged": (37, 256, 16, 128, 1000),
    "wrap": (37, 300, 8, 128, INT32_MAX - 60),
}


def _assert_result_bits(got, want):
    for key, w in want.items():
        g = np.atleast_1d(np.asarray(got[key]))
        w = np.atleast_1d(np.asarray(w))
        assert g.dtype == w.dtype and g.shape == w.shape, (key, g, w)
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8),
                                      err_msg=key)


@pytest.mark.parametrize("case", sorted(DENSE_CASES))
@pytest.mark.parametrize("kind", ["packed", "i32"])
def test_one_vs_many_rows_match_plain_reference(kind, case):
    n, m, bn, bm, lo = DENSE_CASES[case]
    q, u8, base = _ordered_rows(np.random.default_rng(11), n, m, lo)
    logical = _wrap32(u8.astype(np.int64) + base[:, None])
    if kind == "packed":
        got = ops.classify_packed(jnp.asarray(q), jnp.asarray(u8),
                                  jnp.asarray(base), bn=bn, bm=bm,
                                  interpret=True)
    else:
        got = ops.classify_i32(jnp.asarray(q), jnp.asarray(logical),
                               bn=bn, bm=bm, interpret=True)
    want = _ovm_reference(q, logical, bm)
    assert want["q_le_p"].any() and want["p_le_q"].any()
    _assert_result_bits(got, want)


@pytest.mark.parametrize("case", ["hot_ragged", *sorted(DENSE_CASES)])
def test_hybrid_rows_match_plain_reference(case):
    rng = np.random.default_rng(12)
    H, V = 13, 40
    n, m, bn, bm, lo = DENSE_CASES.get(case, DENSE_CASES["rows_ragged"])
    if case == "hot_ragged":
        H, bn = 21, 8
    q, u8, base = _ordered_rows(rng, n, m, lo)
    logical = _wrap32(u8.astype(np.int64) + base[:, None])
    rows = np.stack([rng.integers(V - 3, V + 4, H),
                     rng.integers(0, 2, H)]).astype(np.int32)
    hot_sums = (4 * rows.sum(axis=0)).astype(np.float32)
    got, (bn_run, _) = ops.classify_hybrid(
        jnp.asarray(q), V, rows, hot_sums, jnp.asarray(u8),
        jnp.asarray(base), bn=bn, bm=bm, interpret=True)
    assert H % bn_run, "the hot set must end inside a row block"
    tail = _ovm_reference(q, logical, bm)
    v, npriv = rows
    zero = np.zeros(H, np.float32)
    want = {
        "q_le_p": np.concatenate([V <= v, tail["q_le_p"]]),
        "p_le_q": np.concatenate([(v <= V) & (npriv == 0), tail["p_le_q"]]),
        "sum_q": tail["sum_q"],
        "sum_p": np.concatenate([hot_sums, tail["sum_p"]]),
        "fp_q_before_p": np.concatenate([zero, tail["fp_q_before_p"]]),
        "fp_p_before_q": np.concatenate([zero, tail["fp_p_before_q"]]),
    }
    assert want["q_le_p"][:H].any() and want["p_le_q"][:H].any()
    _assert_result_bits(got, want)


def test_engine_specs_all_valid_and_distinct():
    seen = set()
    for name, spec in ENGINE_SPECS.items():
        validate(spec)                       # structural
        validate(spec, "interpret")          # and within the CI budget
        assert emit(spec) is emit(spec), name  # emission is cached
        assert spec not in seen, f"duplicate spec behind {name}"
        seen.add(spec)


# ---------------------------------------------------------------------------
# generator refusals: malformed specs and VMEM-over-budget knob combos
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(topology="hex"),
    dict(topology="rect", pack="f16"),
    dict(topology="tri", pack="i32"),
    dict(topology="rect", acc="f64"),
    dict(topology="rect", bi=12),                       # not sublane-aligned
    dict(topology="rect", bm=100),                      # not lane-aligned
    dict(topology="rect", pipeline_depth=0),
    dict(topology="mxu"),                               # T missing
    dict(topology="mxu", n_thresholds=8, with_stats=True),
    dict(topology="rect", n_thresholds=8),              # T is mxu-only
    dict(topology="one_vs_many"),                       # stats mandatory
    dict(topology="rect", pack="i32"),                  # stats mandatory
    dict(topology="rect", pack="u8", with_stats=True),
])
def test_generator_refuses_malformed_specs(bad):
    with pytest.raises(ValueError):
        emit(CompareSpec(**bad))


def test_generator_refuses_vmem_over_budget():
    # fine structurally, but one 8-row chunk's int32 difference alone is
    # 1 GiB: over budget on EVERY backend
    spec = CompareSpec(topology="rect", bi=8192, bj=8192, bm=4096)
    assert vmem_estimate(spec) > VMEM_BUDGET["interpret"]
    with pytest.raises(ValueError, match="VMEM estimate"):
        validate(spec, "interpret")
    # emission alone is legal (structure is fine) — the refusal fires
    # when the instance is invoked on a concrete backend
    fn = emit(spec)
    rows = jnp.zeros((1024, 512), jnp.uint8)
    with pytest.raises(ValueError, match="VMEM estimate"):
        fn(rows, rows, None, None, interpret=True)


def test_vmem_estimate_orders_backends_and_depths():
    small = CompareSpec(topology="rect", bi=8, bj=8, bm=128)
    big = CompareSpec(topology="rect", bi=256, bj=256, bm=2048)
    assert vmem_estimate(small) < vmem_estimate(big)
    deeper = CompareSpec(topology="rect", bi=8, bj=8, bm=128,
                         pipeline_depth=3)
    assert vmem_estimate(deeper) > vmem_estimate(small)
    # the tpu budget is the binding one
    assert VMEM_BUDGET["tpu"] < VMEM_BUDGET["interpret"]
    validate(small, "tpu")
    with pytest.raises(ValueError, match="VMEM estimate"):
        validate(big, "tpu")


# ---------------------------------------------------------------------------
# property tests: emitted engines vs the broadcast reference
# ---------------------------------------------------------------------------

def _reference(logical):
    n = logical.shape[0]
    return bc.comparability_matrix(
        bc.BloomClock(logical, jnp.zeros((n,), jnp.int32), 3))


@pytest.mark.parametrize("engine", ["tri", "full", "mxu", "i32"])
def test_emitted_engines_match_reference_property(engine):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(3, 17), mm=st.integers(8, 130),
           seed=st.integers(0, 2**16))
    def check(n, mm, seed):
        rng = np.random.default_rng(seed)
        resid = jnp.asarray(rng.integers(0, 9, (n, mm)), jnp.int32)
        bases = jnp.asarray(rng.integers(0, 5, (n,)), jnp.int32)
        logical = resid + bases[:, None]
        u8, pb, ok = pack.pack_rows(resid, bases)
        assert bool(ok.all())
        ref = _reference(logical)
        got = causal.CausalEngine().pairs(
            causal.PackedSlab(u8, pb), engine=engine)
        np.testing.assert_array_equal(
            np.asarray(got["a_le_b"]), np.asarray(ref["a_le_b"]))
        np.testing.assert_array_equal(
            np.asarray(got["concurrent"]), np.asarray(ref["concurrent"]))

    check()


@pytest.mark.parametrize("pack_mode", ["u8", "i32"])
@pytest.mark.parametrize("engine", ["tri", "full", "mxu"])
def test_emitted_engines_match_reference_deterministic(engine, pack_mode):
    """Always-on (no hypothesis) cross-product: engine x pack mode."""
    rng = np.random.default_rng(5)
    n, mm = 13, 100
    resid = jnp.asarray(rng.integers(0, 9, (n, mm)), jnp.int32)
    bases = jnp.asarray(rng.integers(0, 5, (n,)), jnp.int32)
    logical = resid + bases[:, None]
    ref = _reference(logical)
    if pack_mode == "u8":
        u8, pb, ok = pack.pack_rows(resid, bases)
        assert bool(ok.all())
        slab = causal.PackedSlab(u8, pb)
        got = causal.CausalEngine().pairs(slab, engine=engine)
    else:
        if engine == "mxu":
            pytest.skip("mxu is a packed-only engine")
        got = causal.CausalEngine().pairs(logical, engine=engine)
    np.testing.assert_array_equal(
        np.asarray(got["a_le_b"]), np.asarray(ref["a_le_b"]))
    np.testing.assert_array_equal(
        np.asarray(got["b_le_a"]), np.asarray(ref["a_le_b"]).T)
    np.testing.assert_array_equal(
        np.asarray(got["concurrent"]), np.asarray(ref["concurrent"]))
