"""Observability subsystem: spans, metrics, audit replay, monitor.

Covers the ``repro.obs`` package plus its integration points — the
instrumented anti-entropy session, the socket transport's
skip-and-report behavior for unreachable peers, the scipy-backed
``fork_components``, and the ``mean_strict_fp`` rename regression.

The histogram-merge and span-nesting property tests need ``hypothesis``
(installed in CI); they skip cleanly where it is absent.
"""
import json
import math
import os
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.causal import CausalPolicy
from repro.core import clock as bc
from repro.core.sim import SimConfig, run_gossip_sim
from repro.fleet import ClockRegistry, GossipConfig, fleet_health
from repro.fleet.monitor import (FleetHealth, _fork_components_py,
                                 fork_components, record_health, watch)
from repro.fleet.transport import (ClockNode, ClockPeerServer,
                                   LoopbackTransport, SocketTransport)
from repro.fleet.transport.session import anti_entropy_session
from repro.obs import (NULL_OBSERVER, AuditTrail, FP_LOG10_EDGES, Histogram,
                       MetricsRecorder, NullRecorder, Observer, Tracer,
                       resolve)
from repro.obs import export as obs_export

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # hypothesis is a CI-only extra
    HAVE_HYPOTHESIS = False

M, K = 96, 3


def _clock(row) -> bc.BloomClock:
    return bc.BloomClock(jnp.asarray(row, jnp.int32),
                         jnp.zeros((), jnp.int32), K)


def _fleet(n: int, seed: int = 0, m: int = M) -> dict:
    rng = np.random.default_rng(seed)
    return {f"peer{i}": _clock(rng.integers(0, 25, m)) for i in range(n)}


def _dominating(peers, m: int = M) -> bc.BloomClock:
    cells = np.max([np.asarray(c.logical_cells()) for c in peers.values()],
                   axis=0)
    return _clock(cells + 1)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_nesting_and_jsonl(tmp_path):
    path = tmp_path / "trace.jsonl"
    tr = Tracer(path)
    with tr.span("outer", transport="loopback") as outer:
        with tr.span("inner") as inner:
            inner.set(bytes=42)
        with tr.span("inner2", n=jnp.zeros(3)):    # non-scalar attr
            pass
    tr.close()

    evs = tr.events()
    assert [e["name"] for e in evs] == ["inner", "inner2", "outer"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["outer"]["parent"] is None
    assert by_name["inner"]["parent"] == by_name["outer"]["sid"]
    assert by_name["inner"]["attrs"] == {"bytes": 42}
    # jax arrays stringify instead of breaking serialization
    assert isinstance(by_name["inner2"]["attrs"]["n"], str)
    # children are contained in the parent's interval
    for child in ("inner", "inner2"):
        c, p = by_name[child], by_name["outer"]
        assert c["ts_us"] >= p["ts_us"]
        assert c["ts_us"] + c["dur_us"] <= p["ts_us"] + p["dur_us"]

    spans = obs_export.load_spans(path)
    assert [s["name"] for s in spans] == ["inner", "inner2", "outer"]
    chrome = obs_export.to_chrome(spans)
    assert {e["ph"] for e in chrome["traceEvents"]} == {"X"}
    assert len(chrome["traceEvents"]) == 3


def test_tracer_sibling_spans_do_not_nest():
    tr = Tracer()
    with tr.span("a"):
        pass
    with tr.span("b"):
        pass
    a, b = tr.events()
    assert a["parent"] is None and b["parent"] is None
    assert a["sid"] != b["sid"]


def test_tracer_threads_get_independent_stacks():
    tr = Tracer()
    done = threading.Event()

    def worker():
        with tr.span("worker"):
            done.wait(5.0)

    t = threading.Thread(target=worker)
    with tr.span("main"):
        t.start()
        done.set()
        t.join()
    by_name = {e["name"]: e for e in tr.events()}
    # the worker span must NOT claim "main" as parent: stacks are
    # thread-local
    assert by_name["worker"]["parent"] is None


def _host_events(trace_dir) -> dict:
    """{name: [(start_ns, end_ns)]} of the host events of the one
    ``.xplane.pb`` profile written under ``trace_dir``."""
    import glob

    import jax
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_enabled_spans_land_in_the_profiler_trace(tmp_path):
    """An enabled tracer's span is also a host event of a running JAX
    profiler trace, inside the annotation around it; a null tracer's is
    not, and the JSONL record is what it was."""
    import jax
    from repro.obs import NULL_TRACER
    tr = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with tr.span("registry.fold", n=3):
                jnp.arange(8).sum().block_until_ready()
            with NULL_TRACER.span("registry.null_span"):
                pass
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    (window,), (span,) = events["bench.window"], events["registry.fold"]
    assert window[0] <= span[0] < span[1] <= window[1]
    assert "registry.null_span" not in events
    (rec,) = tr.events()
    assert set(rec) == {"name", "sid", "parent", "ts_us", "dur_us", "pid",
                        "tid", "attrs"}
    assert rec["name"] == "registry.fold" and rec["attrs"] == {"n": 3}


def test_load_spans_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"name": "x"}\n')      # missing sid/ts_us/dur_us
    with pytest.raises(ValueError):
        obs_export.load_spans(bad)
    bad.write_text("not json\n")
    with pytest.raises(ValueError):
        obs_export.load_spans(bad)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_instruments_and_labels():
    rec = MetricsRecorder()
    rec.counter("bytes", phase="digest").inc(10)
    rec.counter("bytes", phase="digest").inc(5)
    rec.counter("bytes", phase="delta").inc(7)
    rec.gauge("occupancy").set(3)
    rec.histogram("fp").observe(1e-6)
    assert rec.counter("bytes", phase="digest").value == 15
    assert rec.counter("bytes", phase="delta").value == 7
    dump = rec.dump()
    assert {(d["kind"], d["name"], tuple(sorted(d["labels"].items())))
            for d in dump} == {
        ("counter", "bytes", (("phase", "digest"),)),
        ("counter", "bytes", (("phase", "delta"),)),
        ("gauge", "occupancy", ()),
        ("histogram", "fp", ()),
    }


def test_histogram_scalar_matches_vector_path():
    vals = [0.0, 1.0, 1e-31, 1e-6, 0.5, 10.0 ** FP_LOG10_EDGES[4]]
    h1, h2 = Histogram(), Histogram()
    h1.observe_many(vals)
    for v in vals:
        h2.observe(v)
    assert (h1.counts == h2.counts).all()
    assert h1.count == h2.count == len(vals)
    assert h1.vmin == h2.vmin and h1.vmax == h2.vmax


def test_histogram_add_counts_shape_guard():
    h = Histogram()
    with pytest.raises(ValueError, match="bin mismatch"):
        h.add_counts(np.zeros(5, np.int64))


def test_histogram_merge_rejects_different_edges():
    with pytest.raises(ValueError, match="different edges"):
        Histogram().merge(Histogram(edges=(0.0, 1.0, 2.0)))


def test_recorder_merge_folds_every_kind():
    a, b = MetricsRecorder(), MetricsRecorder()
    a.counter("n").inc(2)
    b.counter("n").inc(3)
    b.gauge("g").set(7)
    a.histogram("h").observe(1e-4)
    b.histogram("h").observe(1e-8)
    a.merge(b)
    assert a.counter("n").value == 5
    assert a.gauge("g").value == 7.0
    assert a.histogram("h").count == 2


def test_null_recorder_is_falsy_noop():
    rec = NullRecorder()
    assert not rec
    rec.counter("x").inc()
    rec.gauge("x").set(1)
    rec.histogram("x").observe(0.5)
    assert rec.dump() == []


if HAVE_HYPOTHESIS:
    _samples = st.lists(
        st.floats(min_value=0.0, max_value=1.0,
                  allow_nan=False, allow_subnormal=False),
        max_size=40)

    @settings(max_examples=40, deadline=None)
    @given(a=_samples, b=_samples)
    def test_histogram_merge_equals_concatenated_stream(a, b):
        """Merging two histograms == one histogram over the concatenated
        samples: counts/count/min/max exact, total to float tolerance."""
        h1, h2, ref = Histogram(), Histogram(), Histogram()
        h1.observe_many(a)
        h2.observe_many(b)
        ref.observe_many(a + b)
        h1.merge(h2)
        assert (h1.counts == ref.counts).all()
        assert h1.count == ref.count
        assert h1.vmin == ref.vmin and h1.vmax == ref.vmax
        assert math.isclose(h1.total, ref.total,
                            rel_tol=1e-12, abs_tol=1e-12)

    _tree = st.recursive(
        st.just([]),
        lambda kids: st.lists(kids, max_size=3),
        max_leaves=12)

    @settings(max_examples=40, deadline=None)
    @given(tree=_tree)
    def test_span_nesting_invariants(tree):
        """For ANY nesting structure: sids unique, every recorded parent
        id was emitted, children are contained in the parent interval,
        and the recorded tree is exactly the one executed."""
        tr = Tracer()
        shape = []

        def run(subtree, out):
            for i, kids in enumerate(subtree):
                entry = (f"s{len(out)}_{i}", [])
                with tr.span(entry[0]):
                    run(kids, entry[1])
                out.append(entry)

        run(tree, shape)
        evs = tr.events()
        sids = [e["sid"] for e in evs]
        assert len(sids) == len(set(sids))
        by_sid = {e["sid"]: e for e in evs}
        children: dict = {}
        for e in evs:
            if e["parent"] is not None:
                assert e["parent"] in by_sid
                p = by_sid[e["parent"]]
                assert e["ts_us"] >= p["ts_us"]
                assert (e["ts_us"] + e["dur_us"]
                        <= p["ts_us"] + p["dur_us"])
            children.setdefault(e["parent"], []).append(e["name"])

        def names(subtree, prefix_out):
            # children of each node, in execution order
            return [entry[0] for entry in prefix_out]

        # roots recorded == top-level spans executed, in order
        if shape:
            assert children.get(None, []) == [entry[0] for entry in shape]


# ---------------------------------------------------------------------------
# observer wiring
# ---------------------------------------------------------------------------

def test_observer_bool_and_resolve(tmp_path):
    assert not Observer()
    assert Observer(trace=Tracer())
    assert resolve(None) is NULL_OBSERVER
    obs = Observer.to_dir(tmp_path / "run")
    assert obs
    with obs.trace.span("x"):
        pass
    obs.audit.record("verdict", "p0", verdict="ancestor")
    obs.close()
    for name in ("trace.jsonl", "metrics.json", "audit.jsonl"):
        assert (tmp_path / "run" / name).exists(), name


def test_policy_label_excludes_observer():
    """The observer rides the policy without perturbing its identity
    label (cache keys, bench records)."""
    plain = CausalPolicy(fp_threshold=1.0)
    riding = CausalPolicy(fp_threshold=1.0, observer=Observer())
    assert plain.label() == riding.label()
    hash(riding)                           # observer keeps policy hashable


@pytest.mark.parametrize("wide", [False, True])
def test_classify_all_spans_and_readback_bytes(wide):
    """One sweep: a ``registry.classify_all`` root whose children are the
    host dispatch and then the fold, which holds the readback; the
    readback counter sums the bytes read back, 9 a row (status, fp,
    sum) and the query's sum, and the fold counts as a device fold.
    Without an observer the view is the same."""
    capacity = 64
    peers = _fleet(40, seed=3)
    if wide:                                   # a row on the int32 rim
        peers["peer0"] = _clock(np.arange(M) * 4)    # spans past a byte
    obs = Observer(trace=Tracer(), metrics=MetricsRecorder())
    registry = ClockRegistry(capacity=capacity, m=M, k=K,
                             policy=CausalPolicy(observer=obs))
    registry.admit_many(peers)
    local = _dominating(_fleet(40, seed=3))
    view = registry.classify_all(local)
    evs = [e for e in obs.trace.events() if e["name"] != "registry.admit"]
    assert [e["name"] for e in evs] == [
        "causal.classify", "registry.readback", "registry.fold",
        "registry.classify_all"]
    dispatch, readback, fold, root = evs
    assert root["parent"] is None
    assert root["attrs"] == {"n": capacity, "engine": view.engine}
    assert ("wide_overlay" in view.engine) == wide
    assert dispatch["parent"] == fold["parent"] == root["sid"]
    assert readback["parent"] == fold["sid"]
    assert dispatch["ts_us"] + dispatch["dur_us"] <= fold["ts_us"]
    assert fold["ts_us"] <= readback["ts_us"]
    assert (readback["ts_us"] + readback["dur_us"]
            <= fold["ts_us"] + fold["dur_us"])
    counter = obs.metrics.counter("registry_readback_bytes")
    folds = obs.metrics.counter("registry_fold", where="device")
    assert counter.value == 9 * capacity + 4
    assert folds.value == 1
    registry.classify_all(local)
    assert counter.value == 2 * (9 * capacity + 4)
    assert folds.value == 2
    assert obs.metrics.counter("registry_fold", where="host").value == 0

    plain = ClockRegistry(capacity=capacity, m=M, k=K)
    plain.admit_many(peers)
    assert not plain.obs
    ref = plain.classify_all(local)
    np.testing.assert_array_equal(ref.status, view.status)
    np.testing.assert_array_equal(ref.fp, view.fp)


def test_tiered_folds_share_the_registry_fold_span():
    """The tiered registry's warm and cold folds are ``registry.fold``
    spans too, under ``tiers.classify``."""
    from repro.serve.tiers import TierConfig, TieredRegistry
    obs = Observer(trace=Tracer(), metrics=MetricsRecorder())
    tiers = TieredRegistry(
        TierConfig(hot_capacity=6, warm_capacity=10, promote_after=2,
                   demote_batch=2, spill_batch=4, cold_batch=4),
        m=M, k=K, policy=CausalPolicy(observer=obs))
    clocks = _fleet(30, seed=4)
    tiers.admit_many(clocks)
    assert set(tiers._tier_of.values()) == {"hot", "warm", "cold"}
    tiers.classify(_dominating(clocks))
    tiers.close()
    evs = obs.trace.events()
    (top,) = [e for e in evs if e["name"] == "tiers.classify"]
    folds = [e for e in evs if e["name"] == "registry.fold"]
    sweeps = {e["sid"] for e in evs if e["name"] == "registry.classify_all"}
    # the hot slab's own fold, then warm's, then one per cold chunk
    assert len(folds) == 2 + math.ceil(
        sum(t == "cold" for t in tiers._tier_of.values()) / 4)
    assert sum(f["parent"] in sweeps for f in folds) == 1
    assert all(f["parent"] in sweeps | {top["sid"]} for f in folds)


def test_session_spans_metrics_and_audit_loopback():
    peers = _fleet(12, seed=1)
    obs = Observer(trace=Tracer(), metrics=MetricsRecorder(),
                   audit=AuditTrail(store_frames=True))
    policy = CausalPolicy(fp_threshold=1.0, observer=obs)
    registry = ClockRegistry(capacity=16, m=M, k=K, policy=policy)
    registry.admit_many(peers)
    local = _dominating(peers)
    cfg = GossipConfig(policy=policy, straggler_gap=np.inf)
    merged, report = anti_entropy_session(
        registry, local, LoopbackTransport(registry), cfg)

    names = [e["name"] for e in obs.trace.events()]
    assert "gossip.session" in names and "gossip.classify" in names
    assert "gossip.union" in names and "registry.admit" in names
    assert "causal.classify" in names
    sess = next(e for e in obs.trace.events()
                if e["name"] == "gossip.session")
    assert sess["attrs"]["accepted"] == 12

    assert obs.metrics.counter("gossip_sessions",
                               transport="loopback").value == 1
    assert obs.metrics.counter("gossip_peers",
                               outcome="accepted").value == 12
    assert obs.metrics.counter("engine_dispatch", verb="classify",
                               engine="packed").value >= 1
    assert obs.metrics.histogram("fp_claimed").count == 12
    assert obs.metrics.gauge("registry_occupancy").value == 12.0

    verdicts = obs.audit.verdicts()
    assert len(verdicts) == 12
    assert all(v.action == "accept" and v.verdict == "ancestor"
               for v in verdicts)
    # frame replay is standalone: exact even after push-back rewrote
    # the registry rows the verdicts were computed from
    rep = obs.audit.replay_frames(policy=CausalPolicy(fp_threshold=1.0))
    assert rep.ok and rep.matched == rep.checked == 12


def test_audit_live_replay_bit_identity():
    """Without push-back the registry rows stay pristine, so the LIVE
    replay path must re-derive every verdict + fp bit-for-bit."""
    peers = _fleet(10, seed=2)
    obs = Observer(audit=AuditTrail())
    policy = CausalPolicy(fp_threshold=1.0, observer=obs)
    registry = ClockRegistry(capacity=16, m=M, k=K, policy=policy)
    registry.admit_many(peers)
    local = _dominating(peers)
    cfg = GossipConfig(policy=policy, straggler_gap=np.inf,
                       push_back=False)
    anti_entropy_session(registry, local, LoopbackTransport(registry), cfg)
    rep = obs.audit.replay(registry, local)
    assert rep.ok and rep.matched == rep.checked == 10
    assert rep.stale == 0 and not rep.mismatches


def test_audit_trail_jsonl_roundtrip(tmp_path):
    path = tmp_path / "audit.jsonl"
    trail = AuditTrail(path, store_frames=True)
    c = _clock(np.arange(M) % 7)
    from repro.core import wire
    frame = wire.encode_clock(bc.to_wire(c))
    rec = trail.record("verdict", "peerX", verdict="ancestor", fp=1e-7,
                       threshold=1e-4, engine="packed", local_crc=123,
                       peer_crc=456, transport="socket",
                       local_frame=frame, peer_frame=frame)
    trail.record("peer_unreachable", "peerY", transport="socket",
                 detail="ConnectionRefusedError: [Errno 111]")
    trail.annotate_truth(rec, True)
    trail.close()

    loaded = AuditTrail.load(path)
    assert len(loaded) == 2
    got = loaded.records[0]
    assert got.peer_id == "peerX" and got.fp == 1e-7
    assert got.local_frame == frame and got.truth_ok is True
    assert loaded.records[1].kind == "peer_unreachable"
    assert loaded.store_frames
    assert loaded.measured_fp_rate() == 0.0
    assert loaded.mean_predicted_fp() == 1e-7


def test_sim_annotates_audit_with_ground_truth():
    obs = Observer(metrics=MetricsRecorder(),
                   audit=AuditTrail(store_frames=True))
    cfg = GossipConfig(
        policy=CausalPolicy(fp_threshold=1.0, observer=obs),
        straggler_gap=np.inf)
    res = run_gossip_sim(SimConfig(n_nodes=6, n_events=120, m=64, k=3,
                                   seed=0), n_rounds=3, gossip_cfg=cfg)
    assert res.false_negatives == 0
    verdicts = obs.audit.verdicts()
    assert verdicts and all(v.truth_ok is not None for v in verdicts)
    # measured fp sits next to predicted, continuously evaluated
    assert obs.audit.measured_fp_rate() is not None
    assert obs.audit.fp_within_band() is True
    assert obs.metrics.gauge("sim_fp_within_band").value == 1.0
    # every sim verdict replays bit-for-bit from its stored frames
    rep = obs.audit.replay_frames(policy=CausalPolicy(fp_threshold=1.0))
    assert rep.ok and rep.matched == rep.checked == len(verdicts)


# ---------------------------------------------------------------------------
# socket transport: skip-and-report unreachable peers
# ---------------------------------------------------------------------------

def test_socket_session_skips_unreachable_peer():
    peers = _fleet(3, seed=3)
    servers, addresses = [], {}
    try:
        for pid, c in peers.items():
            node = ClockNode(pid, M, K)
            node.set_cells(np.asarray(c.logical_cells()))
            server = ClockPeerServer(node).start()
            servers.append(server)
            addresses[pid] = server.address
        dead = "peer1"
        servers[1].stop()                  # peer1's port now refuses

        obs = Observer(metrics=MetricsRecorder(), audit=AuditTrail())
        policy = CausalPolicy(fp_threshold=1.0, observer=obs)
        registry = ClockRegistry(capacity=8, m=M, k=K, policy=policy)
        tp = SocketTransport(addresses, timeout=5.0)
        cfg = GossipConfig(policy=policy, straggler_gap=np.inf)
        local = _dominating(peers)
        merged, report = anti_entropy_session(registry, local, tp, cfg)

        # the session completed WITHOUT the dead peer and says so
        assert report.unreachable == (dead,)
        assert "unreachable=1" in report.summary()
        assert int(report.n_accepted) == 2
        assert dead in tp.unreachable
        assert dead not in registry
        assert obs.metrics.counter("peer_unreachable",
                                   transport="socket").value == 1
        faults = [r for r in obs.audit.records
                  if r.kind == "peer_unreachable"]
        assert [r.peer_id for r in faults] == [dead]
        assert faults[0].detail          # carries the socket error text

        # the NEXT round still works and still reports it
        _, again = anti_entropy_session(registry, local, tp, cfg)
        assert again.unreachable == (dead,)
    finally:
        for server in servers:
            server.stop()


def test_report_unreachable_defaults_empty():
    peers = _fleet(4, seed=4)
    registry = ClockRegistry(capacity=8, m=M, k=K)
    registry.admit_many(peers)
    _, report = anti_entropy_session(
        registry, _dominating(peers), LoopbackTransport(registry),
        GossipConfig(policy=CausalPolicy(fp_threshold=1.0),
                     straggler_gap=np.inf))
    assert report.unreachable == ()
    assert "unreachable" not in report.summary()


# ---------------------------------------------------------------------------
# monitor: scipy components, rename regression, watch()
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(5))
def test_fork_components_scipy_matches_union_find(seed):
    rng = np.random.default_rng(seed)
    n = 24
    comparable = rng.random((n, n)) < 0.08
    comparable |= comparable.T             # symmetric, like le | ge
    np.fill_diagonal(comparable, False)
    alive = rng.random(n) < 0.8
    got_labels, got_n = fork_components(comparable, alive)
    ref_labels, ref_n = _fork_components_py(comparable, alive)
    np.testing.assert_array_equal(got_labels, ref_labels)
    assert got_n == ref_n
    assert (got_labels[~alive] == -1).all()


def test_fork_components_empty_fleet():
    comparable = np.zeros((4, 4), bool)
    labels, n = fork_components(comparable, np.zeros(4, bool))
    assert n == 0 and (labels == -1).all()


def test_mean_strict_fp_zero_when_no_strict_pairs():
    """Regression for the docstring/field mismatch: the value is the
    mean over STRICT ordered pairs only, and must be 0.0 (not nan)
    when none exist — empty fleet and single-clock fleet."""
    empty = ClockRegistry(capacity=8, m=M, k=K)
    h = fleet_health(empty)
    assert h.mean_strict_fp == 0.0 and not math.isnan(h.mean_strict_fp)

    solo = ClockRegistry(capacity=8, m=M, k=K)
    solo.admit_many({"only": _clock(np.arange(M) % 5)})
    h = fleet_health(solo)
    assert h.mean_strict_fp == 0.0
    # back-compat alias stays readable and equal
    assert h.mean_predicted_fp == h.mean_strict_fp
    assert "mean_strict_fp=" in h.summary()


def test_watch_samples_into_observer_metrics():
    peers = _fleet(6, seed=5)
    obs = Observer(metrics=MetricsRecorder())
    registry = ClockRegistry(capacity=8, m=M, k=K)
    registry.admit_many(peers)
    snaps = list(watch(registry, interval=0.0, samples=3, observer=obs))
    assert len(snaps) == 3
    assert all(isinstance(s, FleetHealth) for s in snaps)
    assert obs.metrics.counter("fleet_health_samples").value == 3
    assert obs.metrics.gauge("fleet_alive").value == 6.0
    assert obs.metrics.histogram(
        "fleet_fp",
        edges=tuple(float(e) for e in snaps[0].fp_bin_edges),
    ).count == int(snaps[0].fp_hist.sum()) * 3


def test_record_health_with_null_metrics_is_noop():
    peers = _fleet(4, seed=6)
    registry = ClockRegistry(capacity=8, m=M, k=K)
    registry.admit_many(peers)
    record_health(fleet_health(registry), NullRecorder())   # must not raise
