"""Quantized-slab validation: u8<->int32 round-trips, overflow
promotion, bit-exactness of every packed compare engine (triangle /
rectangle / MXU thermometer) against the broadcast reference across odd
shapes, alive-masked all_pairs, wire compression, batched checkpoint
lineage, and the autotune table plumbing.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import causal
from repro.core import clock as bc
from repro.fleet import ANCESTOR, DEAD, SAME, ClockRegistry, gossip_round
from repro.kernels import autotune, ops, pack

RNG = np.random.default_rng(11)


def _cells(n, m, hi=20):
    return jnp.asarray(RNG.integers(0, hi, (n, m)), jnp.int32)


def _ticked(c, events):
    for e in events:
        c = bc.tick(c, jnp.uint32(e >> 32), jnp.uint32(e & 0xFFFFFFFF))
    return c


# ---------------------------------------------------------------------------
# pack round-trips and promotion
# ---------------------------------------------------------------------------

def test_pack_roundtrip_exact():
    cells = _cells(9, 300, hi=200)
    u8, base, ok = pack.pack_rows(cells)
    assert bool(ok.all())
    np.testing.assert_array_equal(
        np.asarray(pack.unpack_rows(u8, base)), np.asarray(cells))
    # packing lifts the row minimum into the base
    assert int(jnp.min(u8)) == 0


def test_pack_reports_overflow():
    cells = _cells(4, 64, hi=10)
    cells = cells.at[2, 0].set(1000)          # span > 255 in row 2 only
    u8, base, ok = pack.pack_rows(cells)
    np.testing.assert_array_equal(np.asarray(ok), [True, True, False, True])
    good = np.asarray(ok)
    np.testing.assert_array_equal(
        np.asarray(pack.unpack_rows(u8, base))[good], np.asarray(cells)[good])


@pytest.mark.parametrize("hi", [2, 30, 255])
def test_pack_roundtrip_property(hi):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(vals=st.lists(st.integers(0, hi), min_size=4, max_size=40),
           base=st.integers(0, 2**20))
    def check(vals, base):
        row = jnp.asarray([vals], jnp.int32)
        u8, b, ok = pack.pack_rows(row, jnp.asarray([base], jnp.int32))
        assert bool(ok.all())
        np.testing.assert_array_equal(
            np.asarray(pack.unpack_rows(u8, b)[0]),
            np.asarray(row[0]) + base)

    check()


def test_registry_promotes_and_demotes_wide_rows():
    m, k = 128, 3
    reg = ClockRegistry(capacity=4, m=m, k=k)
    narrow = _ticked(bc.zeros(m, k), range(12))
    wide = bc.BloomClock(
        jnp.zeros((m,), jnp.int32).at[0].set(1000), jnp.zeros((), jnp.int32), k)
    reg.admit_many({"a": narrow, "w": wide})
    assert not reg.packed                      # promotion happened
    # verdicts stay exact through the promoted fallback
    view = reg.classify_all(narrow)
    assert view.status[reg.slot_of("a")] == SAME
    np.testing.assert_array_equal(
        np.asarray(reg.get("w").logical_cells()),
        np.asarray(wide.logical_cells()))
    mats = reg.all_pairs()
    assert not bool(mats["a_le_b"][reg.slot_of("a"), reg.slot_of("w")])
    # overwriting with packable data demotes back to the fast path
    reg.update("w", narrow)
    assert reg.packed
    assert reg.classify_all(narrow).status[reg.slot_of("w")] == SAME


# ---------------------------------------------------------------------------
# packed engines vs broadcast reference (odd shapes, per-row bases)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["tri", "mxu"])
@pytest.mark.parametrize("n,m", [(5, 300), (16, 64), (33, 129), (9, 1000)])
def test_packed_engines_match_reference(engine, n, m):
    resid = jnp.asarray(RNG.integers(0, 9, (n, m)), jnp.int32)
    bases = jnp.asarray(RNG.integers(0, 5, (n,)), jnp.int32)
    resid = resid.at[1].set(resid[0])
    bases = bases.at[1].set(bases[0])          # row 1 == row 0
    logical = resid + bases[:, None]
    u8, pb, ok = pack.pack_rows(resid, bases)
    assert bool(ok.all())
    ref = bc.comparability_matrix(
        bc.BloomClock(logical, jnp.zeros((n,), jnp.int32), 3))
    got = causal.CausalEngine().pairs(
        causal.PackedSlab(u8, pb), engine=engine)
    np.testing.assert_array_equal(np.asarray(got["a_le_b"]),
                                  np.asarray(ref["a_le_b"]))
    np.testing.assert_array_equal(np.asarray(got["b_le_a"]),
                                  np.asarray(ref["a_le_b"]).T)
    np.testing.assert_array_equal(np.asarray(got["concurrent"]),
                                  np.asarray(ref["concurrent"]))
    np.testing.assert_allclose(np.asarray(got["fp"]), np.asarray(ref["fp"]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(got["row_sums"]),
                               np.asarray(jnp.sum(logical, axis=1)))


def test_packed_rect_engine_matches_reference():
    n, m, mm = 12, 17, 200
    a = jnp.asarray(RNG.integers(0, 9, (n, mm)), jnp.int32)
    b = jnp.asarray(RNG.integers(0, 9, (m, mm)), jnp.int32)
    b = b.at[0].set(a[0])
    au8, ab, _ = pack.pack_rows(a)
    bu8, bb, _ = pack.pack_rows(b)
    got = ops.compare_packed(au8, ab, bu8, bb, engine="full", bi=128,
                             bj=128, bm=512, uniform_base=False,
                             interpret=True)
    le = jnp.all(a[:, None, :] <= b[None, :, :], axis=2)
    ge = jnp.all(a[:, None, :] >= b[None, :, :], axis=2)
    np.testing.assert_array_equal(np.asarray(got["a_le_b"]), np.asarray(le))
    np.testing.assert_array_equal(np.asarray(got["b_le_a"]), np.asarray(ge))


def test_multi_tile_accumulation_packed():
    """Dominance violated ONLY in the last m-tile: catches bad cross-tile
    accumulation in the packed triangle engine (pads + revisits)."""
    n, m = 9, 1000
    a = jnp.zeros((n, m), jnp.int32)
    a = a.at[0, m - 1].set(5)
    got = causal.CausalEngine().pairs(a)      # auto -> packed triangle
    le = np.asarray(got["a_le_b"])
    assert not le[0, 1] and le[1, 0]
    assert float(np.asarray(got["row_sums"])[0]) == 5.0


def test_compare_matrix_wide_span_falls_back():
    """Value span > 255 silently uses the int32 engine, same results."""
    n, m = 6, 100
    c = _cells(n, m, hi=5)
    c = c.at[0, 0].set(100000)
    ref = bc.comparability_matrix(
        bc.BloomClock(c, jnp.zeros((n,), jnp.int32), 3))
    got = causal.CausalEngine().pairs(c)
    np.testing.assert_array_equal(np.asarray(got["a_le_b"]),
                                  np.asarray(ref["a_le_b"]))


# ---------------------------------------------------------------------------
# alive-masked all_pairs
# ---------------------------------------------------------------------------

def test_all_pairs_masks_dead_slots():
    m, k = 128, 3
    reg = ClockRegistry(capacity=8, m=m, k=k)
    base_clock = _ticked(bc.zeros(m, k), range(10))
    reg.admit_many({
        "a": base_clock,
        "b": _ticked(base_clock, [77]),
        "dead": _ticked(bc.zeros(m, k), range(500, 505)),
    })
    dead_slot = reg.slot_of("dead")
    reg.evict("dead")
    mats = {kk: np.asarray(v) for kk, v in reg.all_pairs().items()}
    sa, sb = reg.slot_of("a"), reg.slot_of("b")
    assert mats["a_le_b"][sa, sb] and not mats["a_le_b"][sb, sa]
    # dead rows/cols report nothing, not stale verdicts
    for key in ("a_le_b", "b_le_a", "concurrent"):
        assert not mats[key][dead_slot].any()
        assert not mats[key][:, dead_slot].any()
    assert mats["fp"][dead_slot].max() == 0.0
    assert mats["row_sums"][dead_slot] == 0.0
    # never-admitted capacity slots behave the same
    empty = [s for s in range(8) if s not in (sa, sb, dead_slot)]
    assert not mats["a_le_b"][empty].any()


# ---------------------------------------------------------------------------
# wire compression
# ---------------------------------------------------------------------------

def test_wire_roundtrip_u8():
    c = _ticked(bc.zeros(256, 4), range(30))
    snap = bc.to_wire(c)
    assert snap["cells"].dtype == np.uint8     # §4 window fits a byte
    back = bc.from_wire(snap)
    np.testing.assert_array_equal(np.asarray(back.logical_cells()),
                                  np.asarray(c.logical_cells()))


def test_wire_falls_back_to_int32():
    c = bc.BloomClock(
        jnp.zeros((64,), jnp.int32).at[0].set(1000),
        jnp.zeros((), jnp.int32), 3)
    snap = bc.to_wire(c)
    assert snap["cells"].dtype != np.uint8
    np.testing.assert_array_equal(
        np.asarray(bc.from_wire(snap).logical_cells()),
        np.asarray(c.logical_cells()))


def test_gossip_pushback_reports_u8_wire_cost():
    from repro.core import wire

    m, k = 128, 3
    reg = ClockRegistry(capacity=4, m=m, k=k)
    local = _ticked(bc.zeros(m, k), range(20))
    reg.admit_many({"p1": _ticked(bc.zeros(m, k), range(10)), "p2": local})
    merged, report = gossip_round(reg, local)
    assert report.n_accepted == 2
    # MEASURED: the length of the encoded §4 frame that ships per peer
    # (u8 residuals here), not the old m * cell_bytes model
    frame = wire.encode_clock(bc.to_wire(merged))
    assert len(frame) == wire.clock_frame_nbytes(m, packed=True)
    assert report.pushback_bytes == 2 * len(frame)
    view = reg.classify_all(merged)
    for pid in ("p1", "p2"):
        assert view.status[reg.slot_of(pid)] == SAME


# ---------------------------------------------------------------------------
# batched checkpoint lineage
# ---------------------------------------------------------------------------

def test_classify_checkpoints_directory(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    from repro.runtime.clock_runtime import ClockConfig, ClockRuntime, LineageStatus

    rt = ClockRuntime(ClockConfig(m=128, k=3, fp_threshold=1.0))
    mgr = CheckpointManager(str(tmp_path), keep=0)
    state = {"w": np.zeros(2)}
    for step in (1, 2, 3):
        rt.tick_step(step)
        mgr.save(step, state, rt.snapshot(), block=True)
    # move past the checkpoints, then fork an alternate history
    rt.tick_step(99)
    forked = ClockRuntime(ClockConfig(m=128, k=3), run_id="other")
    forked.tick_step(1)
    mgr.save(4, state, forked.snapshot(), block=True)

    lineage = rt.classify_checkpoints(mgr)
    np.testing.assert_array_equal(lineage.steps, [1, 2, 3, 4])
    assert lineage.status[:3] == [LineageStatus.ANCESTOR] * 3
    assert lineage.status[3] == LineageStatus.FORKED
    np.testing.assert_array_equal(lineage.safe, [True, True, True, False])
    assert lineage.latest_safe() == 3

    step, _ = rt.admit_restore_latest(mgr)
    assert step == 3
    # batch verdicts agree with the one-at-a-time path
    for s, status, ok in zip(lineage.steps, lineage.status, lineage.safe):
        _, man = [e for e in mgr.clock_manifests() if e[0] == s][0]
        ok1, st1, _ = rt.admit_restore(rt.clock_from_snapshot(man["clock"]))
        assert (st1, ok1) == (status, ok)


def test_classify_checkpoints_empty(tmp_path):
    from repro.checkpoint.manager import CheckpointManager
    from repro.runtime.clock_runtime import ClockConfig, ClockRuntime

    rt = ClockRuntime(ClockConfig(m=64, k=3))
    lineage = rt.classify_checkpoints(CheckpointManager(str(tmp_path)))
    assert lineage.latest_safe() is None and len(lineage.status) == 0


# ---------------------------------------------------------------------------
# autotune plumbing
# ---------------------------------------------------------------------------

def test_autotune_vmem_model_scales():
    small = autotune.vmem_bytes("tri", 8, 8, 128)
    big = autotune.vmem_bytes("tri", 128, 128, 512)
    assert small < big
    # the thermometer contracts one threshold at a time: its working set
    # grows with the tile, not with the span budget T
    assert autotune.vmem_bytes("mxu", 8, 8, 128, n_thresholds=32) == \
        autotune.vmem_bytes("mxu", 8, 8, 128, n_thresholds=8)
    assert autotune.vmem_bytes("mxu", 128, 128, 128, n_thresholds=8) > \
        autotune.vmem_bytes("mxu", 8, 8, 128, n_thresholds=8)


def test_autotune_table_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "table.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(path))
    key = autotune.key_for("matrix", 1000, 1000, 1000, True)
    autotune.save_table({key: {"engine": "tri", "bi": 64, "bj": 64, "bm": 256}})
    # bucketed lookup: any shape in the same pow2 band hits the entry
    cfg = autotune.lookup("matrix", 700, 700, 600, True)
    assert cfg == {"engine": "tri", "bi": 64, "bj": 64, "bm": 256}
    assert autotune.lookup("matrix", 2000, 2000, 600, True) is None


def test_autotune_measured_sweep_small():
    best = autotune.autotune_matrix(16, 128, span=10, interpret=True)
    assert best["engine"] in ("tri", "i32", "mxu")
    assert best["us"] > 0

# ---------------------------------------------------------------------------
# sparse promoted-row dispatch (one wide row must NOT sink the slab)
# ---------------------------------------------------------------------------

def _one_wide_registry(cap=8, m=128, k=3):
    reg = ClockRegistry(capacity=cap, m=m, k=k)
    rows = {f"p{i}": _ticked(bc.zeros(m, k), range(3 * i, 3 * i + 6))
            for i in range(cap - 1)}
    wide = bc.BloomClock(
        jnp.zeros((m,), jnp.int32).at[2].set(4000),
        jnp.zeros((), jnp.int32), k)
    rows["wide"] = wide
    reg.admit_many(rows)
    assert not reg.packed
    return reg


def test_sparse_promoted_classify_dispatch(monkeypatch):
    """Regression pin: with ONE promoted row, classify_all keeps the
    O(N) bulk on the packed kernel and runs the int32 kernel on just the
    [1, m] promoted handful — never on the whole materialized slab.

    Spies on the kernel runners the CausalEngine front-door calls."""
    reg = _one_wide_registry()
    calls = {"packed": [], "i32": []}
    orig_packed = ops.classify_packed
    orig_i32 = ops.classify_i32
    monkeypatch.setattr(
        ops, "classify_packed",
        lambda q, p, b, **kw: calls["packed"].append(p.shape)
        or orig_packed(q, p, b, **kw))
    monkeypatch.setattr(
        ops, "classify_i32",
        lambda q, p, **kw: calls["i32"].append(p.shape)
        or orig_i32(q, p, **kw))
    local = reg.get("p0")
    view = reg.classify_all(local)
    assert calls["packed"] == [(8, 128)]       # bulk stayed packed
    assert calls["i32"] == [(1, 128)]          # only the promoted handful
    # verdicts stay exact through the overlay
    assert view.status[reg.slot_of("p0")] == SAME
    assert view.status[reg.slot_of("wide")] != DEAD
    assert float(view.sums[reg.slot_of("wide")]) == 4000.0


def test_sparse_promoted_all_pairs_dispatch(monkeypatch):
    """Regression pin: all_pairs with one promoted row sweeps the packed
    engine over the packed rows and the int32 rim over [1, m] x alive."""
    reg = _one_wide_registry()
    calls = {"packed": [], "i32": []}
    orig_packed = ops.compare_packed
    orig_i32 = ops.compare_i32
    monkeypatch.setattr(
        ops, "compare_packed",
        lambda c, b, *a, **kw: calls["packed"].append(c.shape)
        or orig_packed(c, b, *a, **kw))
    monkeypatch.setattr(
        ops, "compare_i32",
        lambda r, c, **kw: calls["i32"].append((r.shape, c.shape))
        or orig_i32(r, c, **kw))
    mats = {kk: np.asarray(v) for kk, v in reg.all_pairs().items()}
    assert calls["packed"] == [(7, 128)]               # bulk: packed rows only
    assert calls["i32"] == [((1, 128), (8, 128))]      # rim: wide vs alive
    # exactness vs a host reference over the logical cells
    logical = np.asarray(reg.cells)
    le_ref = np.all(logical[:, None, :] <= logical[None, :, :], axis=2)
    np.testing.assert_array_equal(mats["a_le_b"], le_ref)
    np.testing.assert_array_equal(mats["b_le_a"], le_ref.T)
    np.testing.assert_array_equal(mats["concurrent"], ~(le_ref | le_ref.T))
    np.testing.assert_array_equal(mats["row_sums"], logical.sum(1))


def test_sparse_promoted_all_pairs_masks_dead(monkeypatch):
    """Dead slots stay silent on the sparse promoted path too."""
    reg = _one_wide_registry()
    dead = reg.slot_of("p3")
    reg.evict("p3")
    mats = {kk: np.asarray(v) for kk, v in reg.all_pairs().items()}
    for key in ("a_le_b", "b_le_a", "concurrent"):
        assert not mats[key][dead].any() and not mats[key][:, dead].any()
    assert mats["fp"][dead].max() == 0.0 and mats["row_sums"][dead] == 0.0


# ---------------------------------------------------------------------------
# autotune fallback: table miss and corrupted cache file
# ---------------------------------------------------------------------------

def test_autotune_table_miss_falls_back(tmp_path, monkeypatch):
    """No row for this backend/shape bucket: lookup reports the miss and
    the engine falls back to the built-in defaults deterministically."""
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(tmp_path / "missing.json"))
    assert autotune.load_table() == {}
    assert autotune.lookup("matrix", 16, 16, 128, True) is None
    c = _cells(16, 128, hi=9)
    got1 = causal.CausalEngine().pairs(c)
    got2 = causal.CausalEngine().pairs(c)
    ref = bc.comparability_matrix(
        bc.BloomClock(c, jnp.zeros((16,), jnp.int32), 3))
    np.testing.assert_array_equal(np.asarray(got1["a_le_b"]),
                                  np.asarray(ref["a_le_b"]))
    np.testing.assert_array_equal(np.asarray(got1["a_le_b"]),
                                  np.asarray(got2["a_le_b"]))
    assert (np.asarray(got1["fp"]) == np.asarray(got2["fp"])).all()


def test_autotune_corrupted_cache_file(tmp_path, monkeypatch):
    """A truncated/garbage cache file must read as an empty table (miss
    everywhere), not crash the compare path."""
    path = tmp_path / "corrupt.json"
    path.write_text('{"matrix|interpret|N16|M16|m128": {"engine": "tr')
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(path))
    assert autotune.load_table() == {}
    assert autotune.lookup("matrix", 16, 16, 128, True) is None
    c = _cells(12, 128, hi=9)
    got = causal.CausalEngine().pairs(c)
    ref = bc.comparability_matrix(
        bc.BloomClock(c, jnp.zeros((12,), jnp.int32), 3))
    np.testing.assert_array_equal(np.asarray(got["a_le_b"]),
                                  np.asarray(ref["a_le_b"]))
    np.testing.assert_allclose(np.asarray(got["fp"]), np.asarray(ref["fp"]),
                               atol=1e-6)
