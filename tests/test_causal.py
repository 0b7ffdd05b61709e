"""Unified causality API validation.

Pins the acceptance contract of the front-door redesign:

- ``CausalEngine.classify`` / ``.pairs`` outputs are BIT-IDENTICAL
  (flags, Eq. 3 fp bits, sums) to the ``kernels.ops`` runners called
  with explicit blocks, across every engine path — int32 fallback,
  packed triangle, MXU thermometer, promoted-row overlay, and sharded
  {1, 2, 3, 4, 8} (3 exercises the odd-d mirror shipping of the halved
  ppermute ring);
- every result is labelled (``engine``, ``blocks``) by its own call,
  also when calls of different engines interleave on two threads;
- the typed results are real pytrees: flatten/unflatten round-trips
  under jit, vmap, and device_put onto a sharded mesh;
- ``Comparison.confident(t)`` is equivalent to the pre-existing
  ``happened_before(a, b, threshold=t)`` decision rule (hypothesis).
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import causal
from repro.core import clock as bc
from repro.fleet import ClockRegistry, GossipConfig, fleet_health, gossip_round
from repro.kernels import ops, pack
from repro.launch.mesh import make_fleet_mesh
from repro.runtime.clock_runtime import ClockConfig, ClockRuntime

RNG = np.random.default_rng(42)
# the runners' blocks the front-door picks off the chip at these small
# shapes (no autotune entry): one-vs-many and all-pairs defaults
OVM = {"bn": 128, "bm": 512}
MAT = {"bi": 128, "bj": 128, "bm": 512}
MATRIX_KEYS = ("a_le_b", "b_le_a", "concurrent", "fp", "row_sums", "col_sums")
CLASSIFY_KEYS = ("q_le_p", "p_le_q", "sum_q", "sum_p",
                 "fp_q_before_p", "fp_p_before_q")


def _cells(n, m, hi=20):
    return jnp.asarray(RNG.integers(0, hi, (n, m)), jnp.int32)


def _clock(row, k=3):
    return bc.BloomClock(jnp.asarray(row, jnp.int32),
                         jnp.zeros((), jnp.int32), k)


def _assert_bits(got, ref, keys):
    for k in keys:
        assert (np.asarray(got[k]) == np.asarray(ref[k])).all(), k


# ---------------------------------------------------------------------------
# typed pairwise compare; front-door vs the kernel runners
# ---------------------------------------------------------------------------

def test_compare_typed_matches_ordering():
    a, b = _clock(_cells(1, 64)[0]), _clock(_cells(1, 64)[0])
    c = causal.compare(a, b)
    o = bc.ordering(a, b)
    assert bool(c.before()) == bool(o.a_le_b)
    assert bool(c.after()) == bool(o.b_le_a)
    assert bool(c.concurrent()) == bool(o.concurrent)
    assert bool(c.equal()) == bool(o.equal)
    assert float(c.fp_ab) == float(o.fp_a_before_b)
    assert float(c.fp_ba) == float(o.fp_b_before_a)


def test_ops_shims_warn_and_are_bit_identical():
    """The front-door's packed pairs and int32 classify equal the
    runners at the blocks it names."""
    cells = _cells(9, 100)
    u8, base, ok = pack.pack_rows(cells)
    assert bool(ok.all())
    eng = causal.CausalEngine()
    got = eng.pairs(causal.PackedSlab(u8, base))
    assert (got.engine, dict(got.blocks)) == ("tri", MAT)
    ref = ops.compare_packed(u8, base, engine="tri", **MAT,
                             uniform_base=False, interpret=True)
    _assert_bits(got, ref, MATRIX_KEYS)
    cres = eng.classify(cells[0], cells)
    assert (cres.engine, cres.blocks) == ("i32", None)
    cref = ops.classify_i32(cells[0], cells, bn=8, bm=512, interpret=True)
    _assert_bits(cres, cref, CLASSIFY_KEYS)


# ---------------------------------------------------------------------------
# engine paths vs runners: i32 fallback, packed tri, mxu, forced i32
# ---------------------------------------------------------------------------

def test_pairs_auto_pack_matches_shim():
    cells = _cells(13, 129, hi=9)          # span fits a byte -> packed tri
    got = causal.CausalEngine().pairs(cells)
    assert got.engine == "tri"
    lo = int(cells.min())
    u8 = (cells - lo).astype(jnp.uint8)
    ref = ops.compare_packed(u8, jnp.full((13,), lo, jnp.int32),
                             engine="tri", **MAT, uniform_base=True,
                             interpret=True)
    _assert_bits(got, ref, MATRIX_KEYS)


def test_pairs_wide_span_i32_fallback_matches_shim():
    cells = _cells(7, 65, hi=5).at[0, 0].set(100000)   # span > U8_MAX
    got = causal.CausalEngine().pairs(cells)
    assert got.engine == "i32"
    ref = ops.compare_i32(cells, cells, **MAT, interpret=True)
    _assert_bits(got, ref, MATRIX_KEYS)


@pytest.mark.parametrize("engine", ["tri", "mxu", "full"])
def test_pairs_forced_packed_engines_match_shim(engine):
    resid = jnp.asarray(RNG.integers(0, 9, (12, 200)), jnp.int32)
    bases = jnp.asarray(RNG.integers(0, 5, (12,)), jnp.int32)
    u8, pb, ok = pack.pack_rows(resid, bases)
    assert bool(ok.all())
    got = causal.CausalEngine(causal.CausalPolicy(engine=engine)).pairs(
        causal.PackedSlab(u8, pb))
    assert got.engine == engine
    bounds = ops.logical_bounds(u8, pb, u8, pb)
    ref = ops.compare_packed(u8, pb, engine=engine, **MAT,
                             uniform_base=bool((pb == pb[0]).all()),
                             interpret=True,
                             bounds=bounds if engine == "mxu" else None)
    _assert_bits(got, ref, MATRIX_KEYS)


def test_pairs_policy_pack_off_forces_i32():
    cells = _cells(6, 64, hi=9)
    got = causal.CausalEngine(causal.CausalPolicy(pack=False)).pairs(cells)
    assert got.engine == "i32"
    ref = ops.compare_i32(cells, cells, **MAT, interpret=True)
    _assert_bits(got, ref, MATRIX_KEYS)


def test_pairs_wide_slab_without_base_host():
    """base_host is an optional perf hint: the promoted-row host path
    must work (device uniform-base probe) when it is absent, and match
    the base_host-carrying call bit for bit."""
    cells = _cells(6, 80, hi=9)
    u8, base, _ = pack.pack_rows(cells)
    wide_row = np.zeros(80, np.int64)
    wide_row[2] = 7000
    eng = causal.CausalEngine()
    got = eng.pairs(causal.PackedSlab(u8, base, wide={4: wide_row}))
    ref = eng.pairs(causal.PackedSlab(u8, base, base_host=np.asarray(base),
                                      wide={4: wide_row}))
    assert got.engine.endswith("+wide_rim")
    _assert_bits(got, ref, MATRIX_KEYS)
    # promoted row's true values drive the verdicts
    assert bool(got["row_sums"][4] == 7000.0)


def test_classify_wide_overlay_matches_shim_composition():
    """PackedSlab with a promoted row: the front-door's overlay equals
    the runners' composition (packed bulk, the promoted row through the
    int32 runner, patched in)."""
    cells = _cells(8, 96, hi=9)
    u8, base, _ = pack.pack_rows(cells)
    wide_row = np.zeros(96, np.int64)
    wide_row[5] = 4000
    slab = causal.PackedSlab(u8, base, wide={3: wide_row})
    q = cells[0]
    got = causal.CausalEngine().classify(q, slab)
    assert got.engine == "packed+wide_overlay"
    ref = dict(ops.classify_packed(q, u8, base, **OVM, interpret=True))
    wide = ops.classify_i32(q, jnp.asarray(wide_row[None], jnp.int32),
                            bn=8, bm=512, interpret=True)
    for key in ("q_le_p", "p_le_q", "sum_p",
                "fp_q_before_p", "fp_p_before_q"):
        ref[key] = ref[key].at[3].set(wide[key][0])
    _assert_bits(got, ref, CLASSIFY_KEYS)


# ---------------------------------------------------------------------------
# sharded paths: bit-identity for shard counts {1, 2, 3, 4, 8}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", [1, 2, 3, 4, 8])
def test_engine_sharded_bit_identical(host_devices, shards):
    """Front-door classify/pairs over a mesh-sharded slab vs unsharded.

    shards=3 pins the odd-d path of the HALVED ppermute ring (every
    visiting offset ships its mirror block back transposed); even
    counts pin the self-mirrored half-way step."""
    cap, m = 24, 160
    cells = _cells(cap, m, hi=9)
    u8, base, ok = pack.pack_rows(cells)
    assert bool(ok.all())
    bh = np.asarray(base)
    q = cells[1]
    ref_eng = causal.CausalEngine()
    ref_cls = jax.device_get(
        ref_eng.classify(q, causal.PackedSlab(u8, base, base_host=bh)))
    ref_pairs = jax.device_get(
        ref_eng.pairs(causal.PackedSlab(u8, base, base_host=bh)))
    mesh = make_fleet_mesh(shards)
    eng = causal.CausalEngine(causal.CausalPolicy(mesh=mesh))
    slab = causal.PackedSlab(u8, base, base_host=bh)
    got_cls = jax.device_get(eng.classify(q, slab))
    _assert_bits(got_cls, ref_cls, CLASSIFY_KEYS)
    got_pairs = jax.device_get(eng.pairs(slab))
    _assert_bits(got_pairs, ref_pairs, MATRIX_KEYS)
    if shards > 1:
        assert got_pairs.engine.startswith("ring_full")


def test_registry_odd_shard_count_bit_identical(host_devices):
    """End-to-end registry equivalence on a 3-shard mesh, dead slots and
    a promoted row included."""
    cap, m, k = 12, 96, 3
    peers = {f"p{i}": _clock(RNG.integers(0, 9, m) + 100 * (i % 2), k)
             for i in range(cap)}
    wide = np.zeros(m, np.int64)
    wide[7] = 3000
    peers["p5"] = _clock(wide, k)
    local = bc.merge(peers["p0"], peers["p1"])

    def build(mesh):
        reg = ClockRegistry(capacity=cap, m=m, k=k, mesh=mesh)
        reg.admit_many(peers)
        reg.evict_many(["p2", "p9"])
        return reg

    ref_reg, got_reg = build(None), build(make_fleet_mesh(3))
    ref_v, got_v = ref_reg.classify_all(local), got_reg.classify_all(local)
    np.testing.assert_array_equal(got_v.status, ref_v.status)
    assert (got_v.fp == ref_v.fp).all() and (got_v.sums == ref_v.sums).all()
    _assert_bits(jax.device_get(got_reg.all_pairs()),
                 jax.device_get(ref_reg.all_pairs()), MATRIX_KEYS)


# ---------------------------------------------------------------------------
# pytree round-trips: jit / vmap / device_put onto a sharded mesh
# ---------------------------------------------------------------------------

def test_comparison_jit_vmap_roundtrip():
    cells_a = _cells(6, 48)
    cells_b = _cells(6, 48)
    a = bc.BloomClock(cells_a, jnp.zeros((6,), jnp.int32), 3)
    b = bc.BloomClock(cells_b, jnp.zeros((6,), jnp.int32), 3)

    # identity through jit preserves class, values, and accessors
    c = causal.compare(_clock(cells_a[0]), _clock(cells_b[0]))
    cj = jax.jit(lambda x: x)(c)
    assert isinstance(cj, causal.Comparison)
    leaves_ref, treedef = jax.tree_util.tree_flatten(c)
    assert jax.tree_util.tree_structure(cj) == treedef
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves_ref)
    assert bool(rebuilt.confident(0.5)) == bool(c.confident(0.5))

    # vmap over batched clocks == python loop over rows
    vm = jax.vmap(causal.compare)(a, b)
    assert isinstance(vm, causal.Comparison)
    for i in range(6):
        one = causal.compare(_clock(cells_a[i]), _clock(cells_b[i]))
        assert bool(vm.before()[i]) == bool(one.before())
        assert float(vm.fp_ab[i]) == float(one.fp_ab)

    # the confident gate composes under jit with a static threshold
    gated = jax.jit(lambda r: r.confident(1e-3))(vm)
    np.testing.assert_array_equal(
        np.asarray(gated), np.asarray(vm.confident(1e-3)))


def test_classify_result_jit_roundtrip():
    cells = _cells(10, 64)
    res = causal.CausalEngine().classify(cells[0], cells)
    rj = jax.jit(lambda x: x)(res)
    assert isinstance(rj, causal.ClassifyResult)
    _assert_bits(jax.device_get(rj), jax.device_get(res), CLASSIFY_KEYS)
    np.testing.assert_array_equal(
        np.asarray(rj.confident(1e-4)), np.asarray(res.confident(1e-4)))


def test_comparison_matrix_device_put_sharded(host_devices):
    """ComparisonMatrix leaves survive device_put onto a sharded mesh
    with per-rank NamedShardings — flatten/unflatten keeps the class,
    metadata, and every bit."""
    mesh = make_fleet_mesh(4)
    res = causal.CausalEngine().pairs(_cells(16, 64, hi=9))
    shardings = jax.tree.map(
        lambda leaf: NamedSharding(
            mesh, P("fleet", None) if leaf.ndim == 2 else P("fleet")), res)
    put = jax.device_put(res, shardings)
    assert isinstance(put, causal.ComparisonMatrix)
    assert put.engine == res.engine
    assert put.le.sharding.is_equivalent_to(
        NamedSharding(mesh, P("fleet", None)), put.le.ndim)
    _assert_bits(jax.device_get(put), jax.device_get(res), MATRIX_KEYS)
    # accessors still compose under jit on the sharded pytree
    conf = jax.jit(lambda r: r.confident(0.5))(put)
    np.testing.assert_array_equal(np.asarray(conf),
                                  np.asarray(res.confident(0.5)))


def test_mapping_protocol_and_unknown_key():
    res = causal.CausalEngine().pairs(_cells(5, 64, hi=9))
    assert set(res.keys()) == set(MATRIX_KEYS)
    assert dict(res.items()).keys() == set(MATRIX_KEYS)
    with pytest.raises(KeyError):
        res["nope"]


# ---------------------------------------------------------------------------
# confident(t) ≡ happened_before(a, b, threshold=t)
# ---------------------------------------------------------------------------

def test_confident_equiv_happened_before_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    m = 32

    @settings(max_examples=30, deadline=None)
    @given(data=st.data())
    def check(data):
        a_row = data.draw(st.lists(st.integers(0, 6), min_size=m,
                                   max_size=m))
        if data.draw(st.booleans()):
            # force dominance half the time so both gate branches fire
            inc = data.draw(st.lists(st.integers(0, 3), min_size=m,
                                     max_size=m))
            b_row = [x + d for x, d in zip(a_row, inc)]
        else:
            b_row = data.draw(st.lists(st.integers(0, 6), min_size=m,
                                       max_size=m))
        t = data.draw(st.sampled_from([1e-6, 1e-4, 1e-2, 0.5, 0.99]))
        a, b = _clock(a_row), _clock(b_row)
        got = bool(causal.compare(a, b).confident(t))
        ref = bool(bc.happened_before(a, b, threshold=t))
        assert got == ref

    check()


# ---------------------------------------------------------------------------
# every result is labelled by its own call
# ---------------------------------------------------------------------------

def test_internal_callers_are_shim_free(tmp_path):
    """The fleet / runtime / gossip hot paths run — promoted rows and
    dead slots included, so the overlay + rim dispatch is exercised —
    and each result names the engine its own call ran."""
    m, k = 96, 3
    reg = ClockRegistry(capacity=8, m=m, k=k)
    rows = {f"p{i}": _clock(RNG.integers(0, 9, m), k) for i in range(6)}
    wide = np.zeros(m, np.int64)
    wide[0] = 5000
    rows["wide"] = _clock(wide, k)
    reg.admit_many(rows)
    reg.evict("p4")
    local = bc.merge(rows["p0"], rows["p1"])
    rt = ClockRuntime(ClockConfig(m=m, k=k))
    rt.clock = local
    assert reg.classify_all(local).engine == "packed+wide_overlay"
    pairs = reg.all_pairs()
    assert pairs.engine == "tri+wide_rim" and pairs.blocks is None
    _, report = gossip_round(reg, local)
    assert report.view.engine == "packed+wide_overlay"
    fleet_health(reg)
    assert rt.classify_fleet(reg).engine == "packed+wide_overlay"
    rt.admit_merge(rows["p2"])
    res = causal.CausalEngine().pairs(_cells(5, m, hi=9))
    assert (res.engine, dict(res.blocks)) == ("tri", MAT)


def _labelled_call(kind):
    """(call, expected engine, expected blocks) for one front-door path."""
    cap, m = 16, 128
    cells = _cells(cap, m, hi=9)
    u8, base, _ = pack.pack_rows(cells)
    slab = causal.PackedSlab(u8, base, base_host=np.asarray(base))
    q = cells[0]
    if kind == "packed":
        eng = causal.CausalEngine()
        return (lambda: eng.classify(q, slab), "packed",
                tuple(sorted(OVM.items())))
    if kind == "hybrid":
        from repro.hybrid import HybridSlab
        H = 8
        meta = np.stack([np.arange(H), np.zeros(H)], 1).astype(np.int32)
        hslab = HybridSlab(u8, base, hot_meta=meta,
                           hot_sums=np.ones((H, 1), np.float32),
                           local_version=4)
        eng = causal.CausalEngine()
        # the blocks the kernel ran with: clamped to the padded tail
        return (lambda: eng.classify(q, hslab), "fused_hot_tail",
                (("bm", 128), ("bn", 16), ("hot", H), ("tail", cap)))
    if kind == "tri":
        eng = causal.CausalEngine(causal.CausalPolicy(bi=64, bj=64))
        return (lambda: eng.pairs(slab), "tri",
                (("bi", 64), ("bj", 64), ("bm", 512)))
    eng = causal.CausalEngine(causal.CausalPolicy(mesh=make_fleet_mesh(2)))
    return (lambda: eng.pairs(slab), "ring_full",
            tuple(sorted({**MAT, "shards": 2, "strategy": "ring"}.items())))


@pytest.mark.parametrize("kinds", [("packed",), ("hybrid",), ("tri",),
                                   ("ring",), ("packed", "tri"),
                                   ("hybrid", "ring")])
def test_result_labels_come_from_their_own_call(host_devices, kinds):
    """Each result's ``engine``/``blocks`` is its own call's choice; two
    kinds interleave on two threads, each labelled correctly every
    time (a process-wide "last dispatch" would cross the labels)."""
    calls = [_labelled_call(kind) for kind in kinds]
    for call, engine, blocks in calls:
        res = call()
        assert (res.engine, res.blocks) == (engine, blocks)
    if len(calls) == 1:
        return
    start = threading.Barrier(len(calls))
    seen = [[] for _ in calls]

    def run(i):
        call = calls[i][0]
        start.wait()
        for _ in range(6):
            res = call()
            seen[i].append((res.engine, res.blocks))

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(calls))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    for (_, engine, blocks), got in zip(calls, seen):
        assert got == [(engine, blocks)] * 6


# ---------------------------------------------------------------------------
# policy threading
# ---------------------------------------------------------------------------

def test_policy_is_single_source_of_truth():
    pol = causal.CausalPolicy(fp_threshold=0.5, engine="i32", pack=False)
    rt = ClockRuntime(ClockConfig(m=64, k=3, policy=pol))
    assert rt.policy is pol and rt.causal.policy is pol
    reg = rt.make_registry(8)
    assert reg.policy.fp_threshold == 0.5
    assert reg.policy.engine == "i32"
    # GossipConfig gates on the policy's threshold, 1e-4 without one
    assert GossipConfig(policy=pol).fp_gate == 0.5
    assert GossipConfig().fp_gate == 1e-4


def test_policy_validation_and_labels():
    with pytest.raises(ValueError, match="unknown engine"):
        causal.CausalPolicy(engine="warp")
    lab = causal.CausalPolicy(engine="mxu", bi=8, autotune=False).label()
    assert "engine=mxu" in lab and "bi8" in lab and "autotune=off" in lab
    merged = causal.CausalPolicy().merged(engine="tri", bm=256)
    assert merged.engine == "tri" and merged.bm == 256
    assert causal.CausalPolicy().merged() == causal.CausalPolicy()
