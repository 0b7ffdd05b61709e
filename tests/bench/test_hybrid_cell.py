"""CPU rehearsals of the ``hybrid-sweep`` cell at a tiny size, and of
its yardstick: the plain reference against exact vector clocks, the
fused kernel's logical bytes and the new readers.  A sound program
comes out correct; the bfloat16 control and each planted fault (a tail
cell flipped after admission, hot rows shipped with a wrong
``(v, n_private)``) come out not correct; a program without bulk
admission fails at once."""
from __future__ import annotations

import shutil
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run as bench_run  # noqa: E402
from bench.kernels import hybrid as kernel  # noqa: E402
from bench.lib import harness  # noqa: E402
from bench.lib.trace import Op, TraceSummary  # noqa: E402
from bench.reference import bloom as ref  # noqa: E402
from bench.reference import hybrid as ref_h  # noqa: E402

SEED = 2 ** 31 + 91
CELL = "hybrid-sweep"
PEAKS = {"hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("hybrid")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def tiny(root: Path) -> dict:
    bench = harness.load_benchmark(root)
    cfg = harness.load_config(bench, harness.find_cell(bench, CELL)["config"],
                              root)
    cfg.update(sessions=2048, hot_capacity=64, tail_capacity=2048, m=128,
               local_events=64, v_low=56, load_chunk=512,
               reference_chunk=256)
    return cfg


def run(root, variant="program", trace=False):
    result, checks, info = bench_run.run_cell(
        CELL, SEED, 1, trace, require_tpu=False, config=tiny(root),
        variant=variant, root=root)
    return result, {n: (v, lim) for n, v, lim in checks}, info


@pytest.fixture
def flipped_tail_cell(monkeypatch):
    """One cell of each admitted chunk's first tail row raised by one
    after admission, where the program keeps its rows."""
    from repro.hybrid import HybridEngine
    orig = HybridEngine.admit_many

    def admit_many(self, sids, v, events=None):
        sids = list(sids)
        orig(self, sids, v, events)
        self._t_u8[self.sessions[sids[0]].slot, 0] += 1

    monkeypatch.setattr(HybridEngine, "admit_many", admit_many)


@pytest.fixture
def wrong_hot_meta(monkeypatch):
    """Hot rows shipped to the kernel with their private events left
    out: ``n_private`` reads 0 for every hot row."""
    from repro.hybrid import HybridEngine
    orig = HybridEngine.slab

    def slab(self):
        out = orig(self)
        out.hot_meta = out.hot_meta.copy()
        out.hot_meta[:, 1] = 0
        return out

    monkeypatch.setattr(HybridEngine, "slab", slab)


def test_sound_hybrid_store_is_correct(root):
    result, checks, info = run(root)
    assert result["correct"], checks
    assert result["attempted"] > 1 and result["failed"] == 0
    assert info["compiles_in_window"] == 0
    assert info["hybrid_resizes"] == 0
    assert info["mirror_rebuilds_in_window"] == 0
    assert info["hot"] == 64 and info["tail"] == 2048 - 64
    for part in ("hot", "tail"):
        assert all(n > 0 for n in info["verdicts_sweep_0"][part].values())
    assert set(result["metrics"]) == {"setup_s", "sweep_ms"}
    assert checks["fp_max_rel_err"][0] <= checks["fp_max_rel_err"][1]


def test_traced_run_reports_hybrid_metrics(root):
    result, checks, _ = run(root, trace=True)
    assert result["correct"], checks
    metrics = result["metrics"]
    assert {"device_idle.hybrid", "hybrid_slab_ms", "hybrid_view_ms",
            "hybrid_readback_mb"} <= set(metrics)
    assert metrics["hybrid_readback_mb"]["value"] == pytest.approx(
        (14 * 2048 + 4) / 1e6)
    assert 0 < metrics["hybrid_slab_ms"]["value"]
    assert 0 < metrics["hybrid_view_ms"]["value"]


def test_bfloat16_control_is_not_correct(root):
    result, checks, _ = run(root, variant="control")
    assert not result["correct"]
    assert checks["fp_max_rel_err"][0] > checks["fp_max_rel_err"][1]


def test_flipped_tail_cell_is_not_correct(root, flipped_tail_cell):
    result, checks, _ = run(root)
    assert not result["correct"]
    assert checks["sum_mismatches"][0] > 0


def test_wrong_hot_meta_is_not_correct(root, wrong_hot_meta):
    result, checks, _ = run(root)
    assert not result["correct"]
    assert checks["status_mismatches"][0] > 0


def test_program_without_bulk_admission_fails_at_once(root, monkeypatch):
    from repro.hybrid import HybridEngine
    monkeypatch.delattr(HybridEngine, "admit_many")
    with pytest.raises(harness.SetupError, match="admit_many"):
        run(root)


@pytest.mark.parametrize("V", [0, 1, 17, 40])
def test_reference_agrees_with_vector_clocks(V):
    """Exact containment is the vector clock order over two writers (the
    local chain and the session's own), and the reference's bloom rows
    never miss it."""
    from repro.core import vector_clock as vc
    k, m, n = 4, 128, 256
    rng = np.random.default_rng(V)
    v = rng.integers(0, 41, n)
    n_priv = np.where(rng.random(n) < 0.3, rng.integers(1, 4, n), 0)
    offsets = np.concatenate([[0], np.cumsum(n_priv)])
    ids = rng.integers(0, 1 << 32, (int(offsets[-1]), 2), dtype=np.uint64)
    p_le_q, q_le_p = ref_h.exact(v, n_priv, V)
    p = vc.VectorClock(jnp.asarray(np.stack([v, n_priv], -1), jnp.int32))
    q = vc.VectorClock(jnp.broadcast_to(jnp.asarray([V, 0], jnp.int32),
                                        p.vec.shape))
    order = vc.compare(p, q)
    np.testing.assert_array_equal(p_le_q, np.asarray(order.a_le_b))
    np.testing.assert_array_equal(q_le_p, np.asarray(order.b_le_a))
    prefix = ref_h.prefix_cells(40, k, m)
    assert (prefix[1:].sum(axis=1) == k * np.arange(1, 41)).all()
    priv = ref_h.private_cells(offsets, ids.astype(np.int64), k, m, 3 * k)
    cells = prefix[v].astype(np.int64)
    rows, cols = np.nonzero(priv >= 0)
    np.add.at(cells, (rows, priv[rows, cols]), 1)
    b_le, b_ge, sp, _ = ref.order_host(cells, prefix[V])
    assert (b_le | ~p_le_q).all() and (b_ge | ~q_le_p).all()
    np.testing.assert_array_equal(sp, k * (v + n_priv))


@pytest.mark.parametrize("hot,tail,m", [(65536, 983040, 1024), (64, 1984,
                                                                 128)])
def test_hybrid_logical_bytes(hot, tail, m):
    want = tail * m + 4 * tail + 4 * m + 12 * hot + 6 * (hot + tail)
    assert kernel.bytes_moved(hot, tail, m) == want
    assert kernel.least_seconds(hot, tail, m, PEAKS) == pytest.approx(
        want / 819e9)


def test_hybrid_kernel_readers():
    hot, tail, m, sweeps = 65536, 983040, 1024, 4
    t = 20 * kernel.least_seconds(hot, tail, m, PEAKS)     # each at 5%
    name = "%bloom_hybrid_u8.1 = custom-call(u8[983040,1024] %p)"
    ops = [Op(name, "mod", i * 1e9, i * 1e9 + t * 1e9, "d0")
           for i in range(sweeps)]
    ops.append(Op("%bloom_one_vs_many_u8.1", "mod", 0.5e9, 0.6e9, "d0"))
    s = TraceSummary(window=(0.0, 10e9), ops=ops, spans=[], devices=["d0"])
    ctx = {"trace": s, "sweeps": sweeps, "hot": hot, "tail": tail, "m": m,
           "peaks": PEAKS,
           "hybrid_spans": {"hybrid.slab": 0.2, "hybrid.view": 0.1},
           "hybrid_readback_bytes": sweeps * 14_680_068}
    read = {n: harness.load_file_module(
        ROOT / f"bench/metrics/{n}.py").read(ctx)
        for n in ("hybrid_roofline", "hybrid_kernel_ms", "hybrid_slab_ms",
                  "hybrid_view_ms", "hybrid_readback_mb")}
    assert read["hybrid_roofline"] == pytest.approx(5.0)
    assert read["hybrid_kernel_ms"] == pytest.approx(t * 1e3)
    assert read["hybrid_slab_ms"] == pytest.approx(50.0)
    assert read["hybrid_view_ms"] == pytest.approx(25.0)
    assert read["hybrid_readback_mb"] == pytest.approx(14.680068)
    # a program without the spans or the counter: nothing to read
    bare = {"trace": s, "sweeps": sweeps, "hybrid_spans": {}}
    for n in ("hybrid_slab_ms", "hybrid_view_ms", "hybrid_readback_mb"):
        assert harness.load_file_module(
            ROOT / f"bench/metrics/{n}.py").read(bare) is None
