"""The hybrid session store at a deployment's size: bulk admission,
host hashing, the plain reference and the sweep's spans.

The contracts under test:

- **The host double hash is the device one**: ``bloom_indices_host``
  equals ``bloom_indices`` bit for bit at every power-of-two m.
- **One minting path**: ``admit_many`` leaves the tail arrays, the slot
  order and the view exactly as the per-session loop it replaced (kept
  below as the reference), and as a loop of ``admit``; ``demote`` and
  ``resize_tail`` re-mint through the same path bit for bit.
- **The sweep agrees with the plain reference** (``bench/reference``,
  which imports nothing of the program): hot verdicts exact with fp 0,
  tail status and sums equal, zero false negatives against vector clocks.
- **The sweep is spanned and counted**: ``hybrid.classify`` holds
  ``hybrid.slab``, ``causal.classify``, ``hybrid.view`` and
  ``hybrid.observe`` in that order.
- **The row snapshot is rebuilt exactly when the population moves**:
  the hot metadata and the view's row order equal a build from the
  catalog after every step, are rebuilt only after a hot-set move or a
  mirror rebuild, and never change under a view that holds them.
"""
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import vector_clock as vc
from repro.core.hashing import (bloom_indices, bloom_indices_host,
                                stable_event_id)
from repro.hybrid import HybridConfig, HybridEngine, fold_pow2
from repro.hybrid import engine as hybrid_engine
from repro.obs import MetricsRecorder, Observer, Tracer

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.reference import bloom as ref  # noqa: E402
from bench.reference import hybrid as ref_h  # noqa: E402


def _population(n, V, seed, wide=()):
    """(v, offsets, ids) with a quarter private (1 to 3 events); rows in
    ``wide`` carry 300 copies of one event, so their span outgrows a
    byte and they take the exact int32 row."""
    rng = np.random.default_rng(seed)
    v = rng.integers(0, V + 1, n)
    counts = np.where(rng.random(n) < 0.25, rng.integers(1, 4, n), 0)
    counts[list(wide)] = 300
    offsets = np.concatenate([[0], np.cumsum(counts)])
    ids = rng.integers(0, 1 << 32, (int(offsets[-1]), 2), dtype=np.uint64)
    for i in wide:
        ids[offsets[i]:offsets[i + 1]] = ids[offsets[i]]
    return v, offsets, ids.astype(np.int64)


def _engine(n, m=128, V=40, hot=8, **kw):
    eng = HybridEngine(HybridConfig(m=m, k=4, hot_capacity=hot,
                                    tail_capacity=n), **kw)
    eng.advance_local(V)
    return eng


def _old_admit_state(eng, v, offsets, ids):
    """The tail arrays a loop of the replaced per-session ``admit`` left:
    probes by the jnp hash one event at a time, one ``np.add.at`` per
    event, slots popped one by one."""
    k, m, T = eng.k, eng.m, eng.cfg.tail_capacity
    chain = np.asarray([stable_event_id(b"hybrid/local", i)
                        for i in range(eng.local_version)])
    probes = np.asarray(bloom_indices(chain[:, 0].astype(np.uint32),
                                      chain[:, 1].astype(np.uint32), k, m),
                        np.int64)
    u8 = np.zeros((T, m), np.uint8)
    base = np.zeros(T, np.int64)
    sums = np.zeros(T, np.float32)
    wide = {}
    free = list(range(T - 1, -1, -1))
    slots = []
    for i in range(len(v)):
        cells = np.zeros(m, np.int64)
        np.add.at(cells, probes[:v[i]].ravel(), 1)
        for hi, lo in ids[offsets[i]:offsets[i + 1]]:
            np.add.at(cells, np.asarray(bloom_indices(
                np.uint32(hi), np.uint32(lo), k, m), np.int64), 1)
        slot = free.pop()
        b = int(cells.min())
        if (cells - b).max() <= 255:
            u8[slot], base[slot] = (cells - b).astype(np.uint8), b
        else:
            wide[slot] = hybrid_engine._fold_i32(cells)
        sums[slot] = np.float32(cells.sum())
        slots.append(slot)
    return u8, base, sums, wide, slots


def _assert_same_tail(eng, u8, base, sums, wide):
    np.testing.assert_array_equal(eng._t_u8, u8)
    np.testing.assert_array_equal(eng._t_base, base)
    np.testing.assert_array_equal(eng._t_sums, sums)
    assert sorted(eng._t_wide) == sorted(wide)
    for slot, row in wide.items():
        np.testing.assert_array_equal(eng._t_wide[slot], row)


@pytest.mark.parametrize("m", [128 << i for i in range(7)])
def test_host_hash_equals_device_hash(m):
    rng = np.random.default_rng(m)
    hi = rng.integers(0, 1 << 32, 4096, dtype=np.uint64).astype(np.uint32)
    lo = rng.integers(0, 1 << 32, 4096, dtype=np.uint64).astype(np.uint32)
    want = np.asarray(bloom_indices(hi, lo, 4, m))
    got = bloom_indices_host(hi, lo, 4, m)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        bloom_indices_host(hi[7], lo[7], 4, m), want[7])


def test_admit_many_equals_the_per_session_loop(monkeypatch):
    # chunks of 3 rows, so the bulk path crosses chunk boundaries
    monkeypatch.setattr(hybrid_engine, "_MINT_CELLS", 3 * 128)
    n = 40
    v, offsets, ids = _population(n, 40, seed=1, wide=(5, 17))
    bulk = _engine(n)
    bulk.admit_many(range(n), v, (offsets, ids))
    loop = _engine(n)
    for i in range(n):
        loop.admit(i, int(v[i]), ids[offsets[i]:offsets[i + 1]].tolist())
    u8, base, sums, wide, slots = _old_admit_state(bulk, v, offsets, ids)
    assert len(wide) == 2
    for eng in (bulk, loop):
        _assert_same_tail(eng, u8, base, sums, wide)
        assert [eng.sessions[i].slot for i in range(n)] == slots
        assert list(eng.sessions) == list(range(n))
        assert eng._t_free == loop._t_free
    for eng in (bulk, loop):
        for i in (0, 1, 2):
            eng.promote(i)
    a, b = bulk.classify(), loop.classify()
    assert a.sids == b.sids
    for name in ("hot", "q_le_p", "p_le_q", "fp_q_before_p",
                 "fp_p_before_q", "sum_p"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name))
    assert a.sum_q == b.sum_q and "+wide_overlay" in a.engine


def test_admit_many_replaces_and_refuses_bad_input():
    eng = _engine(8)
    eng.admit_many(["a", "b"], [3, 4])
    eng.admit_many(["a"], [5], ([0, 1], [[1, 2]]))
    assert eng.sessions["a"].v == 5 and eng.sessions["a"].events == ((1, 2),)
    assert len(eng._t_free) == 6
    with pytest.raises(ValueError, match="exceeds"):
        eng.admit_many(["c"], [41])
    with pytest.raises(ValueError, match="twice"):
        eng.admit_many(["c", "c"], [1, 2])
    with pytest.raises(ValueError, match="offsets"):
        eng.admit_many(["c"], [1], ([0, 2], [[1, 2]]))
    with pytest.raises(RuntimeError, match="full"):
        eng.admit_many(range(7), [1] * 7)
    assert set(eng.sessions) == {"a", "b"}


def test_demote_and_resize_re_mint_through_one_path():
    n = 24
    v, offsets, ids = _population(n, 40, seed=2, wide=(3,))
    eng = _engine(n, m=256, hot=4)
    eng.admit_many(range(n), v, (offsets, ids))
    before = {i: eng._tail_logical(eng.sessions[i].slot).copy()
              for i in range(n)}
    for i in (0, 3, 9):
        eng.promote(i)
        eng.demote(i)
        np.testing.assert_array_equal(
            eng._tail_logical(eng.sessions[i].slot), before[i])
    eng.resize_tail(128)
    fresh = _engine(n, m=128, hot=4)
    fresh.admit_many(range(n), v, (offsets, ids))
    for i in range(n):
        np.testing.assert_array_equal(
            eng._tail_logical(eng.sessions[i].slot),
            fresh._tail_logical(fresh.sessions[i].slot))
        np.testing.assert_array_equal(
            eng._tail_logical(eng.sessions[i].slot),
            fold_pow2(before[i], 128))


def test_classify_agrees_with_the_plain_reference():
    H, T, m, k, V = 64, 2048, 128, 4, 64
    n = H + T
    v, offsets, ids = _population(n, V, seed=3)
    v[:40] = V                          # equal and descendant in both parts
    v[H:H + 40] = V
    eng = _engine(n, m=m, V=V, hot=H)
    eng.admit_many(range(n), v, (offsets, ids))
    for i in range(H):
        eng.promote(i)
    view = eng.classify()
    idx = np.asarray(view.sids)
    hot = view.hot
    n_priv = np.diff(offsets)
    assert hot.sum() == H and set(idx[hot]) == set(range(H))
    # hot rows: exact containment, fp exactly 0
    le, ge = ref_h.exact(v[idx], n_priv[idx], V)
    np.testing.assert_array_equal(view.p_le_q[hot], le[hot])
    np.testing.assert_array_equal(view.q_le_p[hot], ge[hot])
    assert not view.fp_q_before_p[hot].any()
    assert not view.fp_p_before_q[hot].any()
    np.testing.assert_array_equal(view.sum_p[hot],
                                  k * (v[idx] + n_priv[idx])[hot])
    # tail rows: the plain reference over its own minted cells
    prefix = ref_h.prefix_cells(V, k, m)
    priv = ref_h.private_cells(offsets, ids, k, m, 3 * k)
    t = idx[~hot]
    cells = prefix[v[t]].astype(np.int64)
    rows, cols = np.nonzero(priv[t] >= 0)
    np.add.at(cells, (rows, priv[t][rows, cols]), 1)
    p_le_q, q_le_p, sp, sq = ref.order_host(cells, prefix[V])
    np.testing.assert_array_equal(view.p_le_q[~hot], p_le_q)
    np.testing.assert_array_equal(view.q_le_p[~hot], q_le_p)
    np.testing.assert_array_equal(view.sum_p[~hot], sp)
    code = ref.verdicts(p_le_q, q_le_p)
    want_fp = ref.claimed_fp(code, sp, sq[0], m)
    got_fp = np.where(code == ref.CODE["ancestor"], view.fp_p_before_q[~hot],
                      np.where(code == ref.CODE["descendant"],
                               view.fp_q_before_p[~hot], 0.0))
    assert ref.fp_rel_err(got_fp, want_fp).max() < 1e-5
    # every verdict occurs in both parts
    for part in (hot, ~hot):
        got = set(ref.verdicts(view.p_le_q[part], view.q_le_p[part]))
        assert got == set(range(4)), got
    # zero false negatives against exact vector clocks
    p = vc.VectorClock(jnp.asarray(np.stack([v[idx], n_priv[idx]], -1)))
    q = vc.VectorClock(jnp.broadcast_to(jnp.asarray([V, 0]), p.vec.shape))
    truth = vc.compare(p, q)
    assert not (np.asarray(truth.a_le_b) & ~view.p_le_q).any()
    assert not (np.asarray(truth.b_le_a) & ~view.q_le_p).any()


def test_sweep_spans_nest_and_counters_count():
    obs = Observer(trace=Tracer(), metrics=MetricsRecorder())
    n = 48
    v, offsets, ids = _population(n, 40, seed=4)
    eng = _engine(n, observer=obs)
    assert eng.engine.policy.observer is obs
    eng.admit_many(range(n), v, (offsets, ids))
    for i in range(4):
        eng.promote(i)
    counter = obs.metrics.counter
    assert counter("hybrid_admitted").value == n
    eng.classify()
    eng.advance_local(1)
    eng.classify()
    assert counter("hybrid_mirror_rebuilds").value == 1
    assert counter("hybrid_readback_bytes").value == 2 * (14 * n + 4)
    events = obs.trace.events()
    (admit,) = [e for e in events if e["name"] == "hybrid.admit_many"]
    assert admit["attrs"] == {"rows": n,
                              "private_events": int(offsets[-1])}
    roots = [e for e in events if e["name"] == "hybrid.classify"]
    assert len(roots) == 2
    for root in roots:
        assert root["parent"] is None
        assert root["attrs"] == {"m": 128, "hot": 4, "tail": n - 4}
        kids = sorted((e for e in events if e["parent"] == root["sid"]),
                      key=lambda e: e["ts_us"])
        assert [e["name"] for e in kids] == [
            "hybrid.slab", "causal.classify", "hybrid.view",
            "hybrid.observe"]
    assert counter("hybrid_hot_rebuilds").value == 1
    eng.promote(4)
    eng.classify()
    assert counter("hybrid_mirror_rebuilds").value == 2
    assert counter("hybrid_hot_rebuilds").value == 2
    assert eng.hot_rebuilds == 2


def _from_catalog(eng):
    """A fresh engine holding ``eng``'s catalog at its geometry and chain,
    promoted in its hot order: every sweep input built anew."""
    sids = list(eng.sessions)
    v, offsets, ids = eng._describe([eng.sessions[s] for s in sids])
    fresh = _engine(eng.cfg.tail_capacity, m=eng.m, V=eng.local_version,
                    hot=eng.cfg.hot_capacity)
    fresh.admit_many(sids, v, (offsets, ids))
    for sid in eng._hot:
        fresh.promote(sid)
    return fresh


def _assert_as_catalog(eng, view):
    """The cached slab and view against the catalog, bit for bit."""
    hot = list(eng._hot.values())
    meta = np.asarray([[s.v, s.n_private] for s in hot],
                      np.int32).reshape(-1, 2)
    slab = eng.slab()
    np.testing.assert_array_equal(slab.hot_meta, meta)
    np.testing.assert_array_equal(
        slab.hot_sums, (eng.k * meta.sum(axis=1, keepdims=True)).astype(
            np.float32))
    fresh = _from_catalog(eng)
    want = fresh.classify()
    assert fresh.hot_rebuilds == 1
    assert view.sids == want.sids == (
        *eng._hot, *(sid for sid, s in eng.sessions.items() if not s.hot))
    np.testing.assert_array_equal(view.hot, np.arange(len(view.sids))
                                  < len(hot))
    for name in ("hot", "q_le_p", "p_le_q", "fp_q_before_p",
                 "fp_p_before_q", "sum_p"):
        np.testing.assert_array_equal(getattr(view, name),
                                      getattr(want, name), err_msg=name)
    assert view.sum_q == want.sum_q
    np.testing.assert_array_equal(slab.hot_meta, fresh.slab().hot_meta)
    np.testing.assert_array_equal(slab.hot_sums, fresh.slab().hot_sums)


#: name -> (step, whether it moves the hot set or rebuilds the mirror)
_STEPS = {
    "advance_local": (lambda eng: eng.advance_local(1), False),
    "touch": (lambda eng: (eng.touch(11), eng.touch(3)), False),
    "promote": (lambda eng: eng.promote(10), True),
    "demote": (lambda eng: eng.demote(0), True),
    "release_hot": (lambda eng: eng.release(1), True),
    "readmit_hot": (lambda eng: eng.admit_many([2], [5], ([0, 1], [[7, 9]])),
                    True),
    "resize_tail": (lambda eng: eng.resize_tail(64), True),
    "release_tail": (lambda eng: eng.release(12), True),
}


@pytest.mark.parametrize("steps", [[name] for name in _STEPS]
                         + [list(_STEPS)],
                         ids=[*_STEPS, "all_in_turn"])
def test_row_snapshot_rebuilds_exactly_when_the_population_moves(steps):
    n = 24
    v, offsets, ids = _population(n, 40, seed=5)
    eng = _engine(n)
    eng.admit_many(range(n), v, (offsets, ids))
    for i in range(4):
        eng.promote(i)
    _assert_as_catalog(eng, eng.classify())
    assert eng.hot_rebuilds == 1
    want = 1
    for name in steps:
        step, moves = _STEPS[name]
        step(eng)
        view = eng.classify()
        want += moves
        assert eng.hot_rebuilds == want, name
        _assert_as_catalog(eng, view)
        # the next sweep, one local event on, reuses the snapshot
        eng.advance_local(1)
        _assert_as_catalog(eng, eng.classify())
        assert eng.hot_rebuilds == want, name


def test_kept_views_keep_their_rows_and_the_snapshot_is_read_only():
    n = 24
    v, offsets, ids = _population(n, 40, seed=6)
    eng = _engine(n)
    eng.admit_many(range(n), v, (offsets, ids))
    for i in range(4):
        eng.promote(i)
    before = eng.classify()
    sids, hot = before.sids, before.hot.copy()
    assert sids[:4] == (0, 1, 2, 3) and hot.sum() == 4
    eng.promote(10)
    eng.demote(0)
    after = eng.classify()
    assert before.sids is sids and before.sids[:4] == (0, 1, 2, 3)
    np.testing.assert_array_equal(before.hot, hot)
    assert after.sids[:4] == (1, 2, 3, 10) and after.sids[4:] != sids[4:]
    assert after.hot is not before.hot
    slab = eng.slab()
    assert slab.hot_meta is eng.slab().hot_meta
    for arr in (slab.hot_meta, slab.hot_sums, after.hot):
        with pytest.raises(ValueError, match="read-only"):
            arr[0] = 1
