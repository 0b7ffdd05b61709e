"""Transport fabric validation.

- wire robustness: truncated / corrupted / unknown-version / oversized
  frames are rejected with clear errors, round-trips are lossless for
  u8-packed AND int32-promoted rows (hypothesis property), including
  rows fetched across registry shard boundaries;
- loopback bit-identity: ``gossip_round`` (now a loopback session) is
  compared mask-for-mask, fp-bit-for-fp-bit, and cell-for-cell against
  a verbatim copy of the PRE-refactor round on the same fixtures;
- socket sessions: identical decisions to loopback from the same peer
  data, delta skipping after convergence, corrupted-push rejection, and
  the audited gossip sim (zero false negatives) over real TCP servers;
- mesh sessions: the ppermute digest ring agrees with the slab and the
  session matches the loopback decisions on a sharded registry;
- ClockRuntime.gossip: the transport argument end-to-end.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import causal
from repro.causal import CausalPolicy
from repro.core import clock as bc
from repro.core import wire
from repro.core.sim import SimConfig, run_gossip_sim
from repro.fleet import (
    ClockNode,
    ClockPeerServer,
    ClockRegistry,
    GossipConfig,
    LoopbackTransport,
    MeshCollectiveTransport,
    SocketTransport,
    anti_entropy_session,
    gossip_round,
)
from repro.fleet import registry as fr
from repro.fleet.transport.socket import TransportError
from repro.launch.mesh import make_fleet_mesh
from repro.launch.peers import PeerSpec, parse_peers
from repro.runtime.clock_runtime import ClockConfig, ClockRuntime

RNG = np.random.default_rng(21)

AUDIT = GossipConfig(policy=CausalPolicy(fp_threshold=1.0))


def _clock(row, k=3) -> bc.BloomClock:
    return bc.BloomClock(jnp.asarray(np.asarray(row), jnp.int32),
                         jnp.zeros((), jnp.int32), k)


def _ticked(c, events):
    for e in events:
        c = bc.tick(c, jnp.uint32(e >> 32), jnp.uint32(e & 0xFFFFFFFF))
    return c


def _fixture_fleet(m=128, k=3, seed=0):
    """Every status kind: ancestor / same / descendant / forked, plus a
    straggler-able laggard and a promoted (>u8 span) row."""
    rng = np.random.default_rng(seed)
    local = _ticked(bc.zeros(m, k), range(30))
    wide = np.zeros(m, np.int64)
    wide[3] = 700                      # span > 255: promoted row
    return {
        "anc": _ticked(bc.zeros(m, k), range(12)),
        "same": local,
        "desc": _ticked(local, range(200, 208)),
        "fork": _ticked(bc.zeros(m, k), range(900, 912)),
        "lag": _ticked(bc.zeros(m, k), range(2)),
        "wide": _clock(wide, k),
        "rand": _clock(rng.integers(0, 6, m), k),
    }, local


# ---------------------------------------------------------------------------
# wire robustness
# ---------------------------------------------------------------------------

def test_wire_roundtrip_u8_and_i32():
    for cells, base in [(np.arange(64) % 7, 3), (np.arange(64) * 100, 0)]:
        c = bc.BloomClock(jnp.asarray(cells, jnp.int32),
                          jnp.asarray(base, jnp.int32), 4)
        frame = wire.encode_clock(bc.to_wire(c))
        back = bc.from_wire(frame)
        np.testing.assert_array_equal(np.asarray(back.logical_cells()),
                                      np.asarray(c.logical_cells()))
        assert back.k == c.k


def test_wire_rejects_truncation_everywhere():
    frame = wire.encode_clock(bc.to_wire(_ticked(bc.zeros(64, 3), range(9))))
    for cut in (0, 1, 2, 5, 13, len(frame) // 2, len(frame) - 1):
        with pytest.raises(wire.WireFormatError, match="truncated"):
            wire.decode_clock(frame[:cut])


def test_wire_rejects_corruption_and_garbage():
    frame = wire.encode_clock(bc.to_wire(_ticked(bc.zeros(64, 3), range(9))))
    # flip one payload byte -> CRC catches it
    bad = bytearray(frame)
    bad[20] ^= 0x40
    with pytest.raises(wire.WireFormatError, match="CRC32 mismatch"):
        wire.decode_clock(bytes(bad))
    # trailing garbage is framing loss, not silently ignored
    with pytest.raises(wire.WireFormatError, match="oversized"):
        wire.decode_clock(frame + b"\x00")
    # wrong magic
    with pytest.raises(wire.WireFormatError, match="magic"):
        wire.decode_clock(b"ZZ" + frame[2:])


def test_wire_rejects_unknown_version_and_dtype():
    frame = bytearray(wire.encode_clock(
        bc.to_wire(_ticked(bc.zeros(64, 3), range(9)))))
    v2 = frame.copy()
    v2[2] = 9
    with pytest.raises(wire.WireFormatError, match="version 9"):
        wire.decode_clock(bytes(v2))
    dt = frame.copy()
    dt[3] = 7                          # unknown cell dtype code
    dt[-4:] = wire._CRC.pack(__import__("zlib").crc32(bytes(dt[:-4])))
    with pytest.raises(wire.WireFormatError, match="dtype code 7"):
        wire.decode_clock(bytes(dt))


def test_digest_roundtrip_and_robustness():
    d = wire.digest_of("node-7", np.arange(32), base=2, k=3)
    frame = wire.encode_digest(d)
    assert wire.decode_digest(frame) == d
    assert d.nbytes == len(frame)
    with pytest.raises(wire.WireFormatError, match="truncated"):
        wire.decode_digest(frame[:5])
    with pytest.raises(wire.WireFormatError, match="peer-id length"):
        wire.decode_digest(frame + b"xx")
    # a flipped header byte (the advertised clock sum) can't silently
    # steer a pull/skip decision
    bad = bytearray(frame)
    bad[10] ^= 0x10
    with pytest.raises(wire.WireFormatError, match="CRC32 mismatch"):
        wire.decode_digest(bytes(bad))
    # non-utf8 peer-id bytes (with a VALID checksum, i.e. an encoder
    # bug rather than line noise) surface as WireFormatError too
    import zlib
    garbled = bytearray(frame[:-4])
    garbled[wire._DIGEST_HDR.size] = 0xFF
    garbled += wire._CRC.pack(zlib.crc32(bytes(garbled)))
    with pytest.raises(wire.WireFormatError, match="not valid utf-8"):
        wire.decode_digest(bytes(garbled))


def test_cells_crc_is_representation_independent():
    logical = np.asarray([7, 9, 7, 8], np.int64)
    assert (wire.cells_crc(logical, 0)
            == wire.cells_crc(logical - 7, 7)
            == wire.cells_crc(logical.astype(np.uint8), 0))


# ---------------------------------------------------------------------------
# loopback bit-identity vs the pre-refactor gossip_round
# ---------------------------------------------------------------------------

def _pre_refactor_gossip_round(registry, local, fp_gate, straggler_gap,
                               push_back):
    """VERBATIM port of the pre-transport ``gossip_round`` body (PR 4
    state) — the behavioral pin the loopback session must match bit for
    bit on masks, merged cells, and Eq. 3 fp."""
    view = registry.classify_all(local)
    alive = view.alive
    quarantined = alive & (view.status == fr.FORKED)
    stragglers = np.zeros_like(alive)
    if alive.any():
        med = float(np.median(view.sums[alive]))
        stragglers = alive & ~quarantined & ((med - view.sums) > straggler_gap)
    comparable = alive & ~quarantined & ~stragglers
    unconfident = comparable & ~view.confident(fp_gate)
    accepted = comparable & ~unconfident
    merged = local
    if accepted.any():
        merged = registry.union(accepted, local)
        merged = bc.compress(merged)
        if push_back:
            registry.broadcast(accepted, merged)
    return merged, dict(accepted=accepted, quarantined=quarantined,
                        stragglers=stragglers, unconfident=unconfident,
                        view=view)


@pytest.mark.parametrize("gate,gap,push", [
    (1.0, np.inf, True),
    (1.0, 10.0, True),
    (1e-4, 64.0, False),
    (0.3, 64.0, True),
])
def test_loopback_session_bit_identical_to_pre_refactor(gate, gap, push):
    peers, local = _fixture_fleet()
    ref_reg = ClockRegistry(capacity=8, m=128, k=3)
    ref_reg.admit_many(peers)
    got_reg = ClockRegistry(capacity=8, m=128, k=3)
    got_reg.admit_many(peers)

    m_ref, r_ref = _pre_refactor_gossip_round(ref_reg, local, gate, gap, push)
    cfg = GossipConfig(policy=CausalPolicy(fp_threshold=gate),
                       straggler_gap=gap, push_back=push)
    m_got, r_got = gossip_round(got_reg, local, cfg)

    for mask in ("accepted", "quarantined", "stragglers", "unconfident"):
        np.testing.assert_array_equal(getattr(r_got, mask), r_ref[mask],
                                      err_msg=mask)
    np.testing.assert_array_equal(r_got.view.status, r_ref["view"].status)
    # fp BITS, not tolerances
    np.testing.assert_array_equal(r_got.view.fp, r_ref["view"].fp)
    np.testing.assert_array_equal(np.asarray(m_got.logical_cells()),
                                  np.asarray(m_ref.logical_cells()))
    assert r_got.transport == "loopback"
    assert r_got.digest_bytes == 0 and r_got.delta_bytes == 0
    # push-back cost is now MEASURED: n_accepted encoded frames
    if push and r_got.n_accepted:
        frame = wire.encode_clock(bc.to_wire(m_got))
        assert r_got.pushback_bytes == len(frame) * r_got.n_accepted
    # and the registries ended in the same state
    np.testing.assert_array_equal(np.asarray(got_reg.cells),
                                  np.asarray(ref_reg.cells))


def test_report_wire_fields_present_on_legacy_path():
    peers, local = _fixture_fleet()
    reg = ClockRegistry(capacity=8, m=128, k=3)
    reg.admit_many(peers)
    _, report = gossip_round(reg, local)
    assert report.wire_bytes == report.pushback_bytes
    assert "loopback" in report.summary()


# ---------------------------------------------------------------------------
# socket transport
# ---------------------------------------------------------------------------

@pytest.fixture
def socket_fleet():
    """Thread-served TCP fleet mirroring ``_fixture_fleet`` peer data."""
    peers, local = _fixture_fleet()
    nodes, servers, addresses = {}, [], {}
    for pid, c in peers.items():
        node = ClockNode(pid, 128, 3)
        node.set_cells(np.asarray(c.logical_cells()))
        server = ClockPeerServer(node).start()
        nodes[pid] = node
        servers.append(server)
        addresses[pid] = server.address
    yield peers, local, nodes, addresses
    for server in servers:
        server.stop()


def test_socket_session_matches_loopback_decisions(socket_fleet):
    peers, local, nodes, addresses = socket_fleet
    loop_reg = ClockRegistry(capacity=8, m=128, k=3)
    loop_reg.admit_many(peers)
    m_ref, r_ref = gossip_round(loop_reg, local, AUDIT)

    sock_reg = ClockRegistry(capacity=8, m=128, k=3)
    transport = SocketTransport(addresses)
    m_got, r_got = anti_entropy_session(sock_reg, local, transport, AUDIT)

    assert r_got.transport == "socket"
    assert r_got.digest_bytes > 0 and r_got.delta_bytes > 0
    # same per-peer verdicts and decisions (slot layouts may differ)
    for pid in peers:
        rs, gs = loop_reg.slot_of(pid), sock_reg.slot_of(pid)
        assert r_ref.view.status[rs] == r_got.view.status[gs], pid
        assert r_ref.view.fp[rs] == r_got.view.fp[gs], pid
        assert r_ref.accepted[rs] == r_got.accepted[gs], pid
        assert r_ref.quarantined[rs] == r_got.quarantined[gs], pid
    np.testing.assert_array_equal(np.asarray(m_got.logical_cells()),
                                  np.asarray(m_ref.logical_cells()))
    # push-back physically reached the accepted peers' processes
    for pid in peers:
        if r_got.accepted[sock_reg.slot_of(pid)]:
            np.testing.assert_array_equal(
                nodes[pid].cells(), np.asarray(m_got.logical_cells()), pid)


def test_socket_second_round_skips_converged_peers(socket_fleet):
    peers, local, nodes, addresses = socket_fleet
    reg = ClockRegistry(capacity=8, m=128, k=3)
    transport = SocketTransport(addresses)
    merged, first = anti_entropy_session(reg, local, transport, AUDIT)
    assert first.delta_bytes > 0
    merged2, second = anti_entropy_session(reg, merged, transport, AUDIT)
    # accepted peers converged to the union and were not re-pulled;
    # only peers the round did NOT push to (quarantined fork) still
    # advertise an unseen digest — and they were already ingested
    assert second.delta_bytes == 0
    assert second.digest_bytes == first.digest_bytes
    np.testing.assert_array_equal(np.asarray(merged2.logical_cells()),
                                  np.asarray(merged.logical_cells()))


def test_socket_rejects_corrupted_push(socket_fleet):
    peers, local, nodes, addresses = socket_fleet
    transport = SocketTransport(addresses)
    frame = bytearray(wire.encode_clock(bc.to_wire(local)))
    frame[18] ^= 0xFF
    before = nodes["anc"].cells()
    with pytest.raises(TransportError, match="CRC32 mismatch"):
        transport.push(["anc"], bytes(frame))
    np.testing.assert_array_equal(nodes["anc"].cells(), before)


def test_socket_rejects_wrong_m_push(socket_fleet):
    peers, local, nodes, addresses = socket_fleet
    transport = SocketTransport(addresses)
    wrong = wire.encode_clock(bc.to_wire(bc.zeros(32, 3)))
    with pytest.raises(TransportError, match="m=32"):
        transport.push(["anc"], wrong)


def test_gossip_sim_socket_transport_no_false_negatives():
    r = run_gossip_sim(
        SimConfig(n_nodes=5, n_events=120, m=64, k=3, seed=3),
        n_rounds=4, transport="socket")
    assert r.transport == "socket"
    assert r.false_negatives == 0
    assert r.within_eq3_band
    # wire costs are measured frame bytes, not models
    assert r.digest_bytes > 0 and r.delta_bytes > 0
    assert r.wire_bytes == r.digest_bytes + r.delta_bytes + r.pushback_bytes


# ---------------------------------------------------------------------------
# mesh-collective transport
# ---------------------------------------------------------------------------

def test_mesh_transport_needs_mesh():
    with pytest.raises(ValueError, match="mesh-sharded registry"):
        MeshCollectiveTransport(ClockRegistry(capacity=4, m=64, k=3))


def test_mesh_digest_ring_matches_slab(host_devices):
    peers, local = _fixture_fleet()
    reg = ClockRegistry(capacity=8, m=128, k=3, mesh=make_fleet_mesh(4))
    reg.admit_many(peers)
    transport = MeshCollectiveTransport(reg)
    digests, nbytes = transport.digests()
    assert nbytes > 0
    assert set(digests) == set(peers)
    sums = np.asarray(reg.sums)
    for pid, d in digests.items():
        slot = reg.slot_of(pid)
        assert d.clock_sum == pytest.approx(float(sums[slot]))
        assert d.m == 128 and d.k == 3


def test_mesh_session_matches_loopback(host_devices):
    peers, local = _fixture_fleet()
    ref_reg = ClockRegistry(capacity=8, m=128, k=3)
    ref_reg.admit_many(peers)
    m_ref, r_ref = gossip_round(ref_reg, local, AUDIT)
    for shards in (2, 4):
        reg = ClockRegistry(capacity=8, m=128, k=3,
                            mesh=make_fleet_mesh(shards))
        reg.admit_many(peers)
        m_got, r_got = anti_entropy_session(
            reg, local, MeshCollectiveTransport(reg), AUDIT)
        assert r_got.transport == "mesh" and r_got.shards == shards
        for mask in ("accepted", "quarantined", "stragglers", "unconfident"):
            np.testing.assert_array_equal(getattr(r_got, mask),
                                          getattr(r_ref, mask), err_msg=mask)
        np.testing.assert_array_equal(r_got.view.fp, r_ref.view.fp)
        np.testing.assert_array_equal(np.asarray(m_got.logical_cells()),
                                      np.asarray(m_ref.logical_cells()))
        assert r_got.pushback_bytes == r_ref.pushback_bytes
        assert r_got.digest_bytes > 0


def test_gossip_sim_mesh_transport_no_false_negatives(host_devices):
    factory = lambda cap, m, k: ClockRegistry(
        capacity=cap, m=m, k=k, mesh=make_fleet_mesh(4))
    r = run_gossip_sim(
        SimConfig(n_nodes=5, n_events=120, m=64, k=3, seed=3),
        n_rounds=4, registry_factory=factory, transport="mesh")
    assert r.transport == "mesh"
    assert r.false_negatives == 0
    assert r.digest_bytes > 0


# ---------------------------------------------------------------------------
# runtime + launch plumbing
# ---------------------------------------------------------------------------

def test_clock_runtime_gossip_default_loopback():
    rt = ClockRuntime(ClockConfig(m=128, k=3,
                                  policy=CausalPolicy(fp_threshold=1.0)))
    for i in range(20):
        rt.tick_step(i)
    reg = rt.make_registry(8)
    reg.admit_many({"behind": bc.zeros(128, 3), "ahead": _ticked(
        rt.clock, range(300, 304))})
    before = np.asarray(rt.clock.logical_cells())
    report = rt.gossip(reg)
    assert report.transport == "loopback"
    assert report.n_accepted == 2
    after = np.asarray(rt.clock.logical_cells())
    assert (after >= before).all() and after.sum() > before.sum()


def test_clock_runtime_gossip_over_socket(socket_fleet):
    peers, local, nodes, addresses = socket_fleet
    rt = ClockRuntime(ClockConfig(m=128, k=3,
                                  policy=CausalPolicy(fp_threshold=1.0)))
    rt.clock = local
    reg = rt.make_registry(8)
    report = rt.gossip(reg, transport=SocketTransport(addresses))
    assert report.transport == "socket"
    assert report.n_accepted > 0
    # the runtime clock absorbed the union
    for pid in peers:
        if report.accepted[reg.slot_of(pid)]:
            assert bool(bc.ordering(peers[pid], rt.clock).a_le_b)


def test_peer_spec_parsing():
    specs = parse_peers("a@127.0.0.1:9001, b@[::1]:9002")
    assert specs[0] == PeerSpec("a", "127.0.0.1", 9001)
    # brackets are syntax, not part of the connectable host
    assert specs[1] == PeerSpec("b", "::1", 9002)
    assert str(specs[0]) == "a@127.0.0.1:9001"
    with pytest.raises(ValueError, match="bad peer spec"):
        parse_peers("nope")
    with pytest.raises(ValueError, match="duplicate"):
        parse_peers("a@h:1,a@h:2")

