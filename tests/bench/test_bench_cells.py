"""CPU rehearsals of whole benchmark runs at tiny sizes: the look for a
chip is skipped, the rest of a run is driven as on the chip.  A sound
program comes out correct; the bfloat16 control and each planted fault
(a flipped verdict, half of each batch left unanswered, a replica clock
that never moves, an update acknowledged but never stored, a rejected
update acknowledged, a sweep that hands back its first answer again)
come out not correct; a cell added as new files plus one
BENCHMARK.json entry runs; and the knee sweep runs a rate.  The session
store's cells are not in BENCHMARK.json (PERF.md, open questions); the
rehearsals add their entries to a copy."""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
import threading
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run as bench_run  # noqa: E402
from bench import sweep as bench_sweep  # noqa: E402
from bench.lib import harness  # noqa: E402

SEED = 2 ** 31 + 77
CELLS = ["store-ycsb-c", "fleet-sweep"]
STORE_CELLS = {"store-ycsb-c": "ycsb-c", "store-ycsb-b": "ycsb-b"}
STORE = {
    "configs": [{"name": "session-store", "source": "YCSB",
                 "file": "bench/configs/session-store.json",
                 "reduced": ["sessions"], "why": "store"}],
    "workloads": [{"name": cell, "config": "session-store", "traffic": mix,
                   "chips": 1, "why": "store"}
                  for cell, mix in STORE_CELLS.items()],
    "end_to_end": [{"name": "verdict_p99_ms", "unit": "ms",
                    "better": "lower", "bound": 0.25, "source": "host_clock",
                    "workloads": list(STORE_CELLS)}],
    "per_layer": [{"name": name, "unit": unit, "better": "lower",
                   "source": "host_clock", "layer": "store",
                   "moves": "verdict_p99_ms", "workloads": ["store-ycsb-c"]}
                  for name, unit in (("gen_lag_p99_ms.latency", "ms"),
                                     ("batch_rows.latency", "rows"),
                                     ("device_idle.latency", "%"))],
}


def checkout(root: Path) -> Path:
    """A checkout at ``root``: ``bench/`` copied, and BENCHMARK.json with
    the session store's entries added."""
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_benchmark()
    for key, entries in STORE.items():
        bench[key] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return checkout(tmp_path_factory.mktemp("bench"))


def tiny(cell: str, root: Path, **over) -> dict:
    bench = harness.load_benchmark(root)
    cfg = harness.load_config(bench, harness.find_cell(bench, cell)["config"],
                              root)
    if cfg["driver"] == "session_store":
        cfg.update(sessions=2048, load_chunk=1024, warmup_requests=64)
        cfg["tiers"] = dict(hot_capacity=2048, warm_capacity=256)
        cfg["pipeline"] = dict(cfg["pipeline"], batch_size=32,
                               queue_depth=256)
    else:
        cfg.update(capacity=1024, peers=1024, load_chunk=512,
                   reference_chunk=256, m=256, events_per_writer=64,
                   lag_events=16, stragglers=2)
        cfg["replica"] = dict(cfg["replica"], versions=1024)
    cfg.update(over)
    return cfg


def run(cell, root, variant="program", trace=False, **over):
    result, checks, info = bench_run.run_cell(
        cell, SEED, 1, trace, require_tpu=False,
        config=tiny(cell, root, **over), rate=300.0
        if cell.startswith("store") else None, variant=variant, root=root)
    return result, dict((n, (v, lim)) for n, v, lim in checks), info


@pytest.fixture
def flipped_verdicts(monkeypatch):
    """The classify kernel's answer altered where it is produced: the
    two dominance directions swapped."""
    from repro.causal.engine import CausalEngine
    orig = CausalEngine.classify

    def classify(self, query, peers, **kw):
        res = orig(self, query, peers, **kw)
        return dataclasses.replace(res, q_le_p=res.p_le_q, p_le_q=res.q_le_p)

    monkeypatch.setattr(CausalEngine, "classify", classify)


@pytest.fixture
def half_batches(monkeypatch):
    """Half of each batch the window collects is left out: those
    requests are never answered."""
    from bench.drivers.session_store import SessionStore
    from bench.lib import openloop
    from repro.serve.pipeline import AdmissionPipeline
    collect, serve = AdmissionPipeline._collect, SessionStore.serve
    on = threading.Event()

    def _collect(self):
        reqs = collect(self)
        return reqs[::2] if on.is_set() else reqs

    def window(self, *args):
        on.set()
        try:
            return serve(self, *args)
        finally:
            on.clear()

    monkeypatch.setattr(AdmissionPipeline, "_collect", _collect)
    monkeypatch.setattr(SessionStore, "serve", window)
    monkeypatch.setattr(openloop, "ANSWER_WAIT_S", 2.0)


@pytest.fixture
def frozen_replica(monkeypatch):
    """The served state never changes: the pipeline classifies every
    batch against the replica's first clock."""
    from bench.lib.openloop import Replica
    monkeypatch.setattr(Replica, "current", lambda self: self.clocks[0])


@pytest.fixture
def dropped_admits(monkeypatch):
    """The served update path returns the store unchanged: admissions
    made by the pipeline's worker are acknowledged but never written."""
    from repro.serve.tiers import TieredRegistry
    orig = TieredRegistry.admit_many

    def admit_many(self, clocks):
        if threading.current_thread().name == "admission-pipeline":
            return None
        return orig(self, clocks)

    monkeypatch.setattr(TieredRegistry, "admit_many", admit_many)


@pytest.fixture
def rejects_acknowledged(monkeypatch):
    """An answer altered where it is produced: every update is
    acknowledged, whatever the gate decided."""
    from repro.serve.pipeline import AdmissionPipeline
    orig = AdmissionPipeline._resolve

    def _resolve(self, req, verdict, fp, *, admitted, **kw):
        return orig(self, req, verdict, fp,
                    admitted=admitted or req.kind == "admit", **kw)

    monkeypatch.setattr(AdmissionPipeline, "_resolve", _resolve)


@pytest.fixture
def stale_sweeps(monkeypatch):
    """The sweep returns its state unchanged: every call after the first
    hands back the first call's view."""
    from repro.fleet import ClockRegistry
    orig = ClockRegistry.classify_all
    first: list = []

    def classify_all(self, local):
        if not first:
            first.append(orig(self, local))
        return first[0]

    monkeypatch.setattr(ClockRegistry, "classify_all", classify_all)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_program_is_correct(cell, root):
    result, checks, info = run(cell, root)
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert info["compiles_in_window"] == 0
    assert set(result["metrics"]) >= {"setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())


def test_sound_related_updates_are_correct(root):
    """Updates the replica has seen all of are admitted, stored and read
    back equal."""
    result, checks, info = run("store-ycsb-b", root,
                               concurrent_fraction=0.0)
    assert result["correct"], checks
    assert info["acknowledged_updates"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(cell, root):
    result, checks, _ = run(cell, root, variant="control")
    assert not result["correct"]
    assert checks["fp_max_rel_err"][0] > checks["fp_max_rel_err"][1]


@pytest.mark.parametrize("cell", CELLS)
def test_flipped_verdict_is_not_correct(cell, root, flipped_verdicts):
    result, checks, _ = run(cell, root)
    assert not result["correct"]
    bad = ("verdict_mismatches" if cell.startswith("store")
           else "status_mismatches")
    assert checks[bad][0] > 0


def test_half_of_each_batch_left_out_is_not_correct(root, half_batches):
    result, checks, _ = run("store-ycsb-c", root)
    assert not result["correct"]
    assert checks["unanswered"][0] > 0
    assert result["failed"] == checks["unanswered"][0]


def test_frozen_replica_is_not_correct(root, frozen_replica):
    result, checks, _ = run("store-ycsb-c", root)
    assert not result["correct"]
    assert checks["fp_max_rel_err"][0] > checks["fp_max_rel_err"][1]


def test_unstored_update_is_not_correct(dropped_admits, root):
    result, checks, _ = run("store-ycsb-b", root,
                            concurrent_fraction=0.0)
    assert not result["correct"]
    assert checks["readback_mismatches"][0] > 0


def test_acknowledged_rejection_is_not_correct(rejects_acknowledged,
                                               root):
    result, checks, info = run("store-ycsb-b", root,
                               concurrent_fraction=1.0)
    assert not result["correct"]
    assert checks["verdict_mismatches"][0] > 0


def test_stale_sweep_is_not_correct(root, stale_sweeps):
    result, checks, info = run("fleet-sweep", root)
    assert info["sweeps"] > 1
    assert not result["correct"]
    assert checks["status_mismatches"][0] > 0


@pytest.mark.parametrize("cell,names", [
    ("store-ycsb-c", {"gen_lag_p99_ms.latency", "batch_rows.latency",
                      "device_idle.latency"}),
    ("fleet-sweep", {"device_idle.sweep"})])
def test_traced_run_reports_per_layer_metrics(cell, names, root):
    result, _, _ = run(cell, root, trace=True)
    assert result["correct"]
    assert names <= set(result["metrics"])
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert len(result["breakdown"]["idle_gaps"]) <= 10


def test_fleet_draws_compared_sweeps_over_the_window(root):
    _, _, info = run("fleet-sweep", root)
    drawn = info["compared_sweeps"]
    assert drawn[0] == 0 and drawn[-1] == info["sweeps"] - 1
    assert len(drawn) == 4 and info["sweeps"] > 64
    assert not info["replica_versions_wrapped"]


def test_new_cell_from_files_alone(tmp_path):
    """A cell added as a new mix file, a new configuration file and one
    BENCHMARK.json entry runs without an edit to any existing file."""
    checkout(tmp_path)
    bench = harness.load_benchmark(tmp_path)
    cfg = tiny("store-ycsb-c", tmp_path, concurrent_fraction=0.0)
    cfg["name"] = "session-store-small"
    (tmp_path / "bench/configs/session-store-small.json").write_text(
        json.dumps(cfg))
    mix = dict(harness.load_traffic("ycsb-c"), update_fraction=0.2,
               key_distribution="uniform")
    (tmp_path / "bench/traffic/uniform-20.json").write_text(json.dumps(mix))
    bench["configs"].append({"name": "session-store-small", "source": "x",
                             "file": "bench/configs/session-store-small.json",
                             "reduced": ["sessions"], "why": "small"})
    bench["workloads"].append({"name": "store-uniform",
                               "config": "session-store-small",
                               "traffic": "uniform-20", "chips": 1,
                               "why": "uniform keys"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "verdict_p99_ms":
            metric["workloads"].append("store-uniform")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    result, checks, _ = bench_run.run_cell(
        "store-uniform", SEED, 1, False, require_tpu=False, rate=300.0,
        root=tmp_path)
    assert result["correct"], checks
    assert set(result["metrics"]) == {"setup_s", "verdict_p99_ms"}


def test_knee_sweep_runs_one_rate(capsys, root):
    cfg = tiny("store-ycsb-c", root, concurrent_fraction=0.0)
    rc = bench_sweep.main(["--config", "session-store", "--traffic",
                           "ycsb-b", "--seed", str(SEED), "--seconds", "1",
                           "--rates", "200"], config=cfg, require_tpu=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    (row,) = out["rates"]
    assert row["rate"] == 200.0 and row["answered"] == row["requests"]
    assert row["p99_ms"] >= row["p50_ms"] > 0
    assert row["compiles_in_window"] == 0
    assert row["checks"]["verdict_mismatches"] == 0
