"""Cost-model autotuner + sharded-strategy dispatch contracts.

Pins the PR 7 search/dispatch behavior: shard-count-explicit table keys
(a d-shard tune can never poison the 1-shard entry), strategy dispatch
(ring vs replicated) for the sharded all-pairs sweep driven from the
table, bit-identity of BOTH strategies against the single-device
triangle, and the two-stage search actually pruning with its analytic
cost model before measuring.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.causal import CausalEngine, CausalPolicy, PackedSlab
from repro.core import clock as bc
from repro.fleet import ClockRegistry
from repro.kernels import autotune, ops, pack
from repro.launch.mesh import make_fleet_mesh

RNG = np.random.default_rng(7)
MAT = {"bi": 128, "bj": 128, "bm": 512}


def _matrix_blocks(engine, n, m, shards=1):
    """The engine's block resolution for an [n, n] compare (interpret)."""
    blocks = CausalEngine().blocks("matrix", n, n, m, interpret=True,
                                   engine=engine, shards=shards)
    return blocks["bi"], blocks["bj"], blocks["bm"]


def _packed_slab(n: int, m: int, hi: int = 9):
    cells = jnp.asarray(RNG.integers(0, hi, (n, m)), jnp.int32)
    u8, base, ok = pack.pack_rows(cells)
    assert bool(ok.all())
    return u8, base


def _plant(monkeypatch, tmp_path, table: dict):
    path = tmp_path / "table.json"
    path.write_text(json.dumps(table))
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(path))
    return path


# ---------------------------------------------------------------------------
# shard-explicit table keys
# ---------------------------------------------------------------------------

def test_key_for_is_shard_explicit():
    k1 = autotune.key_for("matrix", 512, 512, 512, True)
    k2 = autotune.key_for("matrix", 512, 512, 512, True, shards=2)
    assert k1.endswith("|s1") and k2.endswith("|s2") and k1 != k2
    # same bucketing as before on the shape axes
    assert autotune.key_for("matrix", 300, 300, 300, True) == \
        autotune.key_for("matrix", 512, 512, 512, True)


def test_sharded_tune_cannot_poison_one_shard_entry(monkeypatch, tmp_path):
    """Hand-planted conflict: a 2-shard entry and a 1-shard entry for
    the SAME bucketed shape must resolve independently — the historical
    bug resolved ring blocks at the per-shard sub-shape through the
    1-shard key, so tuning one could corrupt the other."""
    good = {"engine": "tri", "bi": 64, "bj": 64, "bm": 512, "us": 1.0}
    poison = {"strategy": "replicated", "bi": 8, "bj": 8, "bm": 128,
              "us": 1.0}
    _plant(monkeypatch, tmp_path, {
        autotune.key_for("matrix", 512, 512, 512, True): good,
        autotune.key_for("matrix_sharded", 512, 512, 512, True,
                         shards=2): poison,
    })
    assert autotune.lookup("matrix", 512, 512, 512, True) == good
    assert autotune.lookup("matrix_sharded", 512, 512, 512, True,
                           shards=2) == poison
    assert autotune.lookup("matrix_sharded", 512, 512, 512, True,
                           shards=4) is None
    # block resolution: the d-shard path reads ONLY the matrix_sharded
    # key at the GLOBAL shape; the 1-shard path keeps its own entry
    assert _matrix_blocks("full", 512, 512, shards=2) == (8, 8, 128)
    assert _matrix_blocks("tri", 512, 512) == (64, 64, 512)


def test_per_shard_subshape_lookup_not_aliased(monkeypatch, tmp_path):
    """A 1-shard entry for shape N/d must NOT leak into the d-shard ring
    for global shape N (whose per-shard blocks are N/d wide)."""
    _plant(monkeypatch, tmp_path, {
        # absurd blocks planted at the sub-shape a 2-shard ring of
        # N=512 used to resolve through
        autotune.key_for("matrix", 256, 256, 512, True):
            {"engine": "full", "bi": 8, "bj": 8, "bm": 128, "us": 1.0},
    })
    assert _matrix_blocks("full", 512, 512, shards=2) == (128, 128, 512)


# ---------------------------------------------------------------------------
# strategy dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy,label", [("replicated", "replicated_"),
                                            ("ring", "ring_full")])
def test_strategy_dispatch_from_table(host_devices, monkeypatch, tmp_path,
                                      strategy, label):
    """The sharded front-door dispatches on the table's matrix_sharded
    entry and labels its result with that decision."""
    n, m = 32, 128
    _plant(monkeypatch, tmp_path, {
        autotune.key_for("matrix_sharded", n, n, m, True, shards=2):
            {"strategy": strategy, "bi": 128, "bj": 128, "bm": 512,
             "us": 1.0},
    })
    u8, base = _packed_slab(n, m)
    ref = jax.device_get(ops.compare_packed(
        u8, base, engine="tri", **MAT, uniform_base=False, interpret=True))
    got = jax.device_get(CausalEngine(CausalPolicy(
        mesh=make_fleet_mesh(2))).pairs(PackedSlab(u8, base)))
    assert got.engine.startswith(label)
    assert dict(got.blocks)["strategy"] == strategy
    assert dict(got.blocks)["shards"] == 2
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k]),
                                      np.asarray(got[k]), err_msg=k)


@pytest.mark.parametrize("shards", (1, 2, 3, 4, 8))
@pytest.mark.parametrize("strategy", ("ring", "replicated"))
def test_explicit_strategies_bit_identical(host_devices, shards, strategy):
    """Both strategies reproduce the single-device triangle bit-for-bit
    at every shard count, non-uniform §4 bases included."""
    n, m = 24, 160
    cells = jnp.asarray(
        RNG.integers(0, 9, (n, m)) + RNG.integers(0, 300, (n, 1)), jnp.int32)
    u8, base, ok = pack.pack_rows(cells)
    assert bool(ok.all())
    kw = dict(**MAT, uniform_base=False, interpret=True)
    ref = jax.device_get(ops.compare_packed(u8, base, engine="tri", **kw))
    mesh = make_fleet_mesh(shards)
    if strategy == "ring":
        got = ops.compare_ring(u8, base, mesh=mesh, axis="fleet", **kw)
    else:
        got = ops.compare_replicated(u8, base, mesh=mesh, axis="fleet",
                                     engine="tri", mesh_outputs=True, **kw)
    got = jax.device_get(got)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k]),
                                      np.asarray(got[k]), err_msg=k)


def test_unknown_strategy_raises(host_devices, monkeypatch, tmp_path):
    """A table entry naming a strategy the engine does not know is
    refused, not silently run as another."""
    _plant(monkeypatch, tmp_path, {
        autotune.key_for("matrix_sharded", 16, 16, 128, True, shards=2):
            {"strategy": "gossip", "us": 1.0},
    })
    u8, base = _packed_slab(16, 128)
    with pytest.raises(ValueError, match="strategy"):
        CausalEngine(CausalPolicy(mesh=make_fleet_mesh(2))).pairs(
            PackedSlab(u8, base))


def test_registry_replicated_strategy_with_dead_and_promoted(
        host_devices, monkeypatch, tmp_path):
    """End-to-end: a table-planted replicated strategy drives the
    registry's sharded all_pairs — dead slots and a promoted row stay
    bit-identical to the unsharded registry."""
    cap, m, k = 16, 128, 3
    _plant(monkeypatch, tmp_path, {
        autotune.key_for("matrix_sharded", cap, cap, m, True, shards=2):
            {"strategy": "replicated", "bi": 128, "bj": 128, "bm": 512,
             "us": 1.0},
    })
    rows = RNG.integers(0, 9, (cap, m))
    rows[5, ::5] = 2000                        # promoted (span > u8)
    peers = {f"p{i}": bc.BloomClock(jnp.asarray(rows[i], jnp.int32),
                                    jnp.zeros((), jnp.int32), k)
             for i in range(cap)}
    ref_reg = ClockRegistry(capacity=cap, m=m, k=k)
    ref_reg.admit_many(peers)
    ref_reg.evict_many(["p2", "p9"])
    reg = ClockRegistry(capacity=cap, m=m, k=k, mesh=make_fleet_mesh(2))
    reg.admit_many(peers)
    reg.evict_many(["p2", "p9"])
    ref = jax.device_get(ref_reg.all_pairs())
    got = jax.device_get(reg.all_pairs())
    assert "replicated" in got.engine
    for key in ("a_le_b", "b_le_a", "concurrent"):
        np.testing.assert_array_equal(np.asarray(got[key], bool),
                                      np.asarray(ref[key], bool),
                                      err_msg=key)
    assert (np.asarray(got["fp"]) == np.asarray(ref["fp"])).all()


# ---------------------------------------------------------------------------
# cost model + pruned search
# ---------------------------------------------------------------------------

def test_predict_cost_vmem_bust_is_infinite():
    assert autotune.predict_cost("tri", 4096, 4096, 4096,
                                 1024, 1024, 4096, False) == math.inf
    assert autotune.predict_cost("tri", 256, 256, 512,
                                 128, 128, 512, True) < math.inf


def test_predict_cost_ranks_step_overhead_on_interpret():
    """Interpret mode is dominated by per-grid-step overhead, so fewer,
    bigger blocks must rank strictly cheaper."""
    few = autotune.predict_cost("tri", 1024, 1024, 1024, 256, 256, 1024, True)
    many = autotune.predict_cost("tri", 1024, 1024, 1024, 8, 8, 128, True)
    assert few < many


def test_predict_sharded_cost_backend_dependent(monkeypatch):
    """Serialized-host meshes (CI) predict replicated; physically
    parallel meshes predict the ring."""
    ring_ci = autotune.predict_sharded_cost("ring", 1024, 1024, 4, True)
    repl_ci = autotune.predict_sharded_cost("replicated", 1024, 1024, 4, True)
    assert repl_ci < ring_ci
    monkeypatch.setattr(autotune, "_host_serialized", lambda interpret: False)
    ring_hw = autotune.predict_sharded_cost("ring", 1024, 1024, 4, False)
    repl_hw = autotune.predict_sharded_cost("replicated", 1024, 1024, 4,
                                            False)
    assert ring_hw < repl_hw


def test_prune_measures_at_most_half(monkeypatch, tmp_path):
    """The measured stage sees at most half the knob grid (and VMEM
    busts never survive), with the counters recording the deltas."""
    _plant(monkeypatch, tmp_path, {})       # isolate the shipped table
    before = dict(autotune.SEARCH_STATS)
    exp = {}
    best = autotune.autotune_matrix(16, 128, span=10, interpret=True,
                                    explain=exp)
    assert best["engine"] in ("tri", "i32", "mxu") and best["us"] > 0
    assert exp["survivors"] <= max(1, exp["grid"] // 2)
    assert len(exp["measured"]) <= exp["survivors"]
    d_cand = autotune.SEARCH_STATS["candidates"] - before["candidates"]
    d_pruned = autotune.SEARCH_STATS["pruned"] - before["pruned"]
    assert d_cand == exp["grid"]
    assert d_pruned == exp["grid"] - exp["survivors"]
    # predicted ranking is exposed for --explain, best-first
    preds = [p["pred_us"] for p in exp["predicted"]]
    assert preds == sorted(preds)


def test_vmem_bytes_matches_template_estimate():
    """The search prices candidates with the SAME model the kernel
    generator refuses over-budget specs with."""
    from repro.kernels.template import CompareSpec, vmem_estimate
    assert autotune.vmem_bytes("tri", 8, 8, 128) == vmem_estimate(
        CompareSpec(topology="tri", pack="u8", bi=8, bj=8, bm=128,
                    pipeline_depth=1))
    assert autotune.vmem_bytes("mxu", 128, 128, 128, 64) == vmem_estimate(
        CompareSpec(topology="mxu", pack="u8", bi=128, bj=128, bm=128,
                    with_base=True, pipeline_depth=1, n_thresholds=64))


def test_autotune_sweep_emits_observer_spans(monkeypatch, tmp_path):
    """autotune_shapes records one autotune.sweep span per (op, shape)
    with search counters, through the standard Observer plumbing."""
    from repro.obs import MetricsRecorder, Observer, Tracer
    _plant(monkeypatch, tmp_path, {})
    obs = Observer(trace=Tracer(), metrics=MetricsRecorder())
    table = autotune.autotune_shapes([(16, 128)], observer=obs,
                                     interpret=True)
    assert len(table) == 3                   # matrix + one_vs_many + hybrid
    spans = [e for e in obs.trace.events() if e["name"] == "autotune.sweep"]
    assert {e["attrs"]["op"] for e in spans} == {"matrix", "one_vs_many",
                                                "hybrid"}
    for e in spans:
        assert "winner" in e["attrs"] and e["attrs"]["measured"] >= 1
