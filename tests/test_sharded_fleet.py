"""Multi-device equivalence harness for the sharded ClockRegistry.

Runs on 8 forced host-platform devices (tests/conftest.py sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax
initializes).  The contract under test is exact: for ANY shard count in
{1, 2, 4, 8}, the shard_map'ed classify_all / all_pairs paths must be
**bit-identical** — flags, Eq. 3 fp values, sums — to the unsharded
packed engines, fleets with dead slots and promoted (wide) rows
included, and the audited gossip sim must keep the paper's §3
zero-false-negative guarantee on a sharded registry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.causal import CausalPolicy
from repro.core import clock as bc
from repro.core.sim import SimConfig, run_gossip_sim
from repro.fleet import (DEAD, ClockRegistry, GossipConfig, fleet_health,
                         gossip_round)
from repro.launch.mesh import make_fleet_mesh
from repro.runtime.clock_runtime import ClockConfig, ClockRuntime

SHARD_COUNTS = (1, 2, 4, 8)
CAP, M, K = 32, 192, 3


def _clock(row) -> bc.BloomClock:
    return bc.BloomClock(jnp.asarray(row, jnp.int32),
                         jnp.zeros((), jnp.int32), K)


def _random_fleet(seed: int, cap: int = CAP, m: int = M):
    """Random peer clocks with per-row offsets (non-uniform §4 bases)."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 20, (cap, m)) + rng.integers(0, 300, (cap, 1))
    return {f"peer{i}": _clock(rows[i]) for i in range(cap)}


def _filled(peers, mesh=None, cap: int = CAP, m: int = M) -> ClockRegistry:
    reg = ClockRegistry(capacity=cap, m=m, k=K, mesh=mesh)
    reg.admit_many(peers)
    return reg


def _evict_some(reg: ClockRegistry, seed: int, n_evict: int = 5):
    rng = np.random.default_rng(1000 + seed)
    gone = rng.choice(sorted(reg.peer_ids()), size=n_evict, replace=False)
    reg.evict_many(list(gone))


def _assert_views_identical(got, ref):
    np.testing.assert_array_equal(got.status, ref.status)
    np.testing.assert_array_equal(got.alive, ref.alive)
    assert (got.fp == ref.fp).all(), "fp must be bit-identical"
    assert (got.sums == ref.sums).all()
    assert got.local_sum == ref.local_sum


def _assert_pairs_identical(got, ref):
    got, ref = jax.device_get(got), jax.device_get(ref)
    for key in ("a_le_b", "b_le_a", "concurrent"):
        np.testing.assert_array_equal(
            np.asarray(got[key], bool), np.asarray(ref[key], bool), err_msg=key)
    assert (np.asarray(got["fp"]) == np.asarray(ref["fp"])).all(), \
        "fp must be bit-identical"
    for key in ("row_sums", "col_sums"):
        assert (np.asarray(got[key]) == np.asarray(ref[key])).all(), key


@pytest.mark.parametrize("seed", range(4))
def test_classify_all_shard_invariance(host_devices, seed):
    """Property: classify_all flags/fp from 1, 2, 4, 8 shards are
    bit-identical to the unsharded packed engine, dead slots included."""
    peers = _random_fleet(seed)
    local = bc.merge(peers["peer0"], peers["peer3"])
    ref_reg = _filled(peers)
    _evict_some(ref_reg, seed)
    ref = ref_reg.classify_all(local)
    for shards in SHARD_COUNTS:
        reg = _filled(peers, mesh=make_fleet_mesh(shards))
        assert reg.n_shards == shards
        _evict_some(reg, seed)
        _assert_views_identical(reg.classify_all(local), ref)


@pytest.mark.parametrize("shards", SHARD_COUNTS)
def test_classify_all_matches_mask_fold_sharded(host_devices, shards,
                                                mask_fold,
                                                views_bit_identical):
    """The device fold runs elementwise on the row-sharded result: the
    view equals the boolean-mask fold of the same result read back, with
    dead slots and an int32-rim row."""
    peers = _random_fleet(21)
    wide = np.zeros(M, np.int64)
    wide[::5] = 700                         # span 700 >> U8_MAX
    peers["peer4"] = _clock(wide)
    local = bc.merge(peers["peer0"], peers["peer3"])
    reg = _filled(peers, mesh=make_fleet_mesh(shards))
    _evict_some(reg, 21)
    assert not reg.packed
    got = reg.classify_all(local)
    want = mask_fold(jax.device_get(reg.engine.classify(local, reg._slab())),
                     reg._alive_host, CAP)
    assert "wide_overlay" in got.engine
    assert (got.status == DEAD).sum() == 5
    views_bit_identical(got, want)


@pytest.mark.parametrize("seed", range(4))
def test_all_pairs_shard_invariance(host_devices, seed):
    """Property: the block-row ppermute ring reproduces the symmetric
    triangle sweep bit-for-bit at every shard count."""
    peers = _random_fleet(seed)
    ref_reg = _filled(peers)
    _evict_some(ref_reg, seed)
    ref = ref_reg.all_pairs()
    for shards in SHARD_COUNTS:
        reg = _filled(peers, mesh=make_fleet_mesh(shards))
        _evict_some(reg, seed)
        _assert_pairs_identical(reg.all_pairs(), ref)


def test_all_pairs_fully_alive_shard_invariance(host_devices):
    """No dead slots: the sharded path returns the ring result directly
    (no host finalize) and must still match the triangle engine."""
    peers = _random_fleet(99)
    ref = _filled(peers).all_pairs()
    for shards in SHARD_COUNTS:
        got = _filled(peers, mesh=make_fleet_mesh(shards)).all_pairs()
        _assert_pairs_identical(got, ref)


def test_sharded_promoted_rows_classify_and_pairs(host_devices):
    """A promoted (span > u8) row keeps both sharded paths exact: the
    packed bulk runs sharded, the wide handful is overlaid int32."""
    peers = _random_fleet(5)
    wide = np.zeros(M, np.int64)
    wide[::7] = 1000                        # span 1000 >> U8_MAX
    peers["peer7"] = _clock(wide)
    local = bc.merge(peers["peer1"], peers["peer2"])
    ref_reg = _filled(peers)
    assert not ref_reg.packed
    ref_view = ref_reg.classify_all(local)
    ref_pairs = ref_reg.all_pairs()
    for shards in (2, 8):
        reg = _filled(peers, mesh=make_fleet_mesh(shards))
        assert not reg.packed
        _assert_views_identical(reg.classify_all(local), ref_view)
        _assert_pairs_identical(reg.all_pairs(), ref_pairs)


def test_gossip_round_sharded_matches_unsharded(host_devices):
    """One anti-entropy round takes identical decisions on a sharded
    registry and reports the shard count."""
    peers = _random_fleet(11)
    local = peers["peer2"]
    cfg = GossipConfig(policy=CausalPolicy(fp_threshold=1.0), push_back=True)
    m_ref, r_ref = gossip_round(_filled(peers), local, cfg)
    for shards in (2, 4):
        reg = _filled(peers, mesh=make_fleet_mesh(shards))
        m_got, r_got = gossip_round(reg, local, cfg)
        np.testing.assert_array_equal(r_got.accepted, r_ref.accepted)
        np.testing.assert_array_equal(r_got.quarantined, r_ref.quarantined)
        np.testing.assert_array_equal(r_got.stragglers, r_ref.stragglers)
        assert r_got.pushback_bytes == r_ref.pushback_bytes
        assert r_got.shards == shards and r_ref.shards == 1
        np.testing.assert_array_equal(
            np.asarray(m_got.logical_cells()), np.asarray(m_ref.logical_cells()))


def test_fleet_health_sharded_matches(host_devices):
    peers = _random_fleet(13)
    ref = fleet_health(_filled(peers))
    got = fleet_health(_filled(peers, mesh=make_fleet_mesh(4)))
    assert got.n_alive == ref.n_alive
    assert got.n_components == ref.n_components
    assert got.comparable_fraction == ref.comparable_fraction
    np.testing.assert_array_equal(got.component, ref.component)
    np.testing.assert_array_equal(got.fp_hist, ref.fp_hist)
    assert got.mean_strict_fp == ref.mean_strict_fp
    assert got.shards == 4 and ref.shards == 1
    assert "shards=4" in got.summary()
    # engine hints that are valid unsharded stay valid sharded (the ring
    # resolves them to its rectangle engine instead of raising)
    hinted = fleet_health(_filled(peers, mesh=make_fleet_mesh(2)),
                          engine="tri")
    assert hinted.n_components == ref.n_components


def test_engine_i32_hint_survives_every_path(host_devices):
    """engine="i32" — the hint the legacy int32 fallback honored —
    keeps working everywhere: fully packed, promoted rows, sharded."""
    packed = _random_fleet(31)
    promoted = dict(packed)
    wide = np.zeros(M, np.int64)
    wide[4] = 3000
    promoted["peer9"] = _clock(wide)
    for peers in (packed, promoted):
        ref = _filled(peers).all_pairs()
        for mesh in (None, make_fleet_mesh(4)):
            got = _filled(peers, mesh=mesh).all_pairs(engine="i32")
            _assert_pairs_identical(got, ref)


@pytest.mark.parametrize("shards", (2, 8))
def test_gossip_sim_sharded_zero_false_negatives(host_devices, shards):
    """§3 on a sharded registry: the audited sim must never call a
    truth-ordered peer FORKED, at any shard count."""
    factory = lambda cap, m, k: ClockRegistry(
        capacity=cap, m=m, k=k, mesh=make_fleet_mesh(shards))
    res = run_gossip_sim(SimConfig(n_nodes=8, n_events=240, m=64, k=3,
                                   seed=3), n_rounds=5,
                         registry_factory=factory)
    assert res.false_negatives == 0
    assert res.rounds == 5 and res.claims > 0
    assert res.within_eq3_band


def test_runtime_make_registry_sharded(host_devices):
    """ClockRuntime builds a mesh-backed registry sized to its config."""
    rt = ClockRuntime(ClockConfig(m=M, k=K))
    reg = rt.make_registry(CAP, mesh=make_fleet_mesh(4))
    assert (reg.m, reg.k, reg.n_shards) == (M, K, 4)
    reg.admit_many(_random_fleet(17))
    view = rt.classify_fleet(reg)
    assert view.alive.all()


def test_registry_capacity_must_divide_shards(host_devices):
    with pytest.raises(ValueError, match="not divisible"):
        ClockRegistry(capacity=30, m=M, k=K, mesh=make_fleet_mesh(4))


# ---------------------------------------------------------------------------
# wire round-trips across shard boundaries
# ---------------------------------------------------------------------------

def _wire_roundtrip(src: ClockRegistry, dst: ClockRegistry):
    """Snapshot every peer of ``src`` in §4 wire form, re-admit into
    ``dst``, and check the logical cells survive losslessly."""
    snaps = {pid: bc.to_wire(src.get(pid)) for pid in src.peer_ids()}
    dst.admit_many({pid: bc.from_wire(s) for pid, s in snaps.items()})
    for pid in src.peer_ids():
        np.testing.assert_array_equal(
            np.asarray(src.get(pid).logical_cells()),
            np.asarray(dst.get(pid).logical_cells()), err_msg=pid)


def test_wire_roundtrip_sharded_to_unsharded(host_devices):
    src = _filled(_random_fleet(21), mesh=make_fleet_mesh(4))
    _wire_roundtrip(src, ClockRegistry(capacity=CAP, m=M, k=K))


def test_wire_roundtrip_unsharded_to_sharded(host_devices):
    src = _filled(_random_fleet(22))
    _wire_roundtrip(src, ClockRegistry(capacity=CAP, m=M, k=K,
                                       mesh=make_fleet_mesh(8)))


def test_wire_roundtrip_across_shard_counts_with_wide_row(host_devices):
    """Promoted rows cross shard boundaries too: wire form falls back to
    int32 cells for them and re-admission preserves them exactly."""
    peers = _random_fleet(23)
    wide = np.zeros(M, np.int64)
    wide[3] = 5000
    peers["peer5"] = _clock(wide)
    src = _filled(peers, mesh=make_fleet_mesh(2))
    dst = ClockRegistry(capacity=CAP, m=M, k=K, mesh=make_fleet_mesh(8))
    _wire_roundtrip(src, dst)
    assert not dst.packed                   # the wide row stayed promoted
