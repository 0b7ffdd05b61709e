"""CPU tests of the benchmark's yardstick: trace reduction, logical
bytes, the open-loop clock, the traffic generator and the result line.
Nothing here loads the TPU library."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench.kernels import ovm  # noqa: E402
from bench.lib import harness, openloop, traffic  # noqa: E402
from bench.lib.trace import Op, Profiler, TraceSummary, _union  # noqa: E402
from bench.reference import bloom as ref  # noqa: E402

PEAKS = {"hbm_bytes_per_s": 819e9}


def _summary(intervals, window=(0.0, 40.0), spans=()):
    ops = [Op(name, "mod", s, e, "d0") for name, s, e in intervals]
    return TraceSummary(window=window, ops=ops, spans=list(spans),
                        devices=["d0"] if ops else [])


def test_union_merges_overlaps():
    assert _union([(0, 10), (5, 15), (20, 30), (25, 26)]) == 25
    assert _union([]) == 0


def test_busy_idle_and_gaps_from_synthetic_trace():
    s = _summary([("a", 0, 10), ("b", 5, 15), ("a", 20, 30)],
                 spans=[("bench.window", 0, 40), ("bench.sweep", 14, 21)])
    assert s.busy_s() == pytest.approx(25e-9)
    assert s.window_s == pytest.approx(40e-9)
    assert s.idle_share() == pytest.approx(15 / 40)
    gaps = s.idle_gaps()
    assert [g[1] for g in gaps] == pytest.approx([10e-9, 5e-9])
    assert gaps[0][0].startswith("unannotated")
    assert gaps[1][0].startswith("bench.sweep")
    assert s.op_seconds(lambda o: o.name == "a") == pytest.approx(20e-9)
    assert s.top_ops()[0] == ["a", pytest.approx(20e-9)]


def test_ops_outside_window_are_clipped():
    s = _summary([("a", -10, 5), ("a", 35, 50)])
    assert s.busy_s() == pytest.approx(10e-9)


def test_recorded_cpu_trace_reduces():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    prof = Profiler(True)
    prof.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.sweep"):
                f(x).block_until_ready()
            time.sleep(0.01)
    prof.stop()
    s = prof.summary
    assert s.window_s > 0.02
    assert s.devices, "no operations found in the CPU trace"
    busy = s.busy_s()
    assert 0 < busy < s.window_s
    assert 0 < s.idle_share() < 1
    kernel = s.op_seconds(lambda o: "dot" in o.name)
    assert 0 < kernel <= sum(o.end_ns - o.start_ns for o in s.ops) / 1e9
    assert any(name.startswith("bench.sweep") for name, *_ in s.spans)
    assert len(s.breakdown()["device_ops"]) <= 10


@pytest.mark.parametrize("n,m", [(1 << 20, 1024), (1 << 20, 256), (8, 128)])
def test_ovm_logical_bytes(n, m):
    want = n * m + 4 * n + 4 * m + 2 * n + 4 * n
    assert ovm.bytes_moved(n, m) == want
    assert ovm.least_seconds(n, m, PEAKS) == pytest.approx(want / 819e9)


def test_ovm_roofline_reader():
    n, m, sweeps = 1 << 20, 1024, 4
    t = 10 * ovm.least_seconds(n, m, PEAKS)     # each call at 10%
    kernel = "%one_vs_many_pallas.1 = custom-call(u8[1048576,1024] %p)"
    rim = "%one_vs_many_pallas.2 = custom-call(s32[16,1024] %w)"
    ops = [(kernel, i * 1e9, i * 1e9 + t * 1e9) for i in range(sweeps)]
    ops += [("copy", 0.5e9, 0.6e9), (rim, 0.7e9, 0.8e9)]
    s = _summary(ops, window=(0.0, 10e9))
    ctx = {"trace": s, "sweeps": sweeps, "rows": n, "m": m, "peaks": PEAKS}
    roof = harness.load_file_module(ROOT / "bench/metrics/ovm_roofline.py")
    ms = harness.load_file_module(ROOT / "bench/metrics/ovm_kernel_ms.py")
    assert roof.read(ctx) == pytest.approx(10.0)
    assert ms.read(ctx) == pytest.approx(t * 1e3)
    assert roof.read(dict(ctx, trace=_summary([("copy", 0, 1)]))) is None


def test_readers_return_nothing_without_data():
    for path in sorted((ROOT / "bench/metrics").glob("[!_]*.py")):
        mod = harness.load_file_module(path)
        assert mod.read({}) is None, path.name


class _Ticket:
    def __init__(self):
        self.ev = threading.Event()

    def result(self, timeout=None):
        if not self.ev.wait(timeout):
            raise TimeoutError
        return "ok"


def test_stall_raises_later_latency_and_generator_lag():
    """A server that stalls the sender for 0.2 s at request 20: every
    request due during the stall is sent late, and its latency, timed
    from its due time, carries the stall."""
    n, stall_at, stall = 60, 20, 0.2
    due = np.arange(n) * 0.005
    pending = []
    lock = threading.Lock()
    stop = threading.Event()

    def server():
        while not stop.is_set():
            with lock:
                for t in pending:
                    t.ev.set()
                pending.clear()
            time.sleep(0.001)

    srv = threading.Thread(target=server, daemon=True)
    srv.start()

    def submit(i):
        if i == stall_at:
            time.sleep(stall)
        t = _Ticket()
        with lock:
            pending.append(t)
        return t

    replica = openloop.Replica([0, 1, 2])
    try:
        served = openloop.serve(due, 0.5, submit, replica, 0.1)
    finally:
        stop.set()
        srv.join(timeout=5)
    lat = served.done - served.due
    lag = served.sent - served.due
    assert served.n_answered == n
    assert lat[:stall_at].max() < 0.1
    assert lag[:stall_at].max() < 0.05
    # requests 20..59 are due within the 0.2 s stall
    hit = slice(stall_at, stall_at + 30)
    assert (lat[hit] > 0.05).all()
    assert (lag[stall_at + 1: stall_at + 30] > 0.04).all()
    assert lat[stall_at + 1] > lat[stall_at + 29]


@pytest.mark.parametrize("mix", ["ycsb-b", "ycsb-c"])
def test_schedule_counts_fixed_by_mix(mix):
    t = harness.load_traffic(mix)
    a = traffic.open_loop(t, 2, 1, 1 << 20)
    b = traffic.open_loop(t, 2, 2 ** 31 + 12345, 1 << 20)
    assert len(a) == len(b) == round(t["rate_per_s"] * 2)
    assert a.update.sum() == b.update.sum()
    assert (np.diff(a.due) >= 0).all() and a.due.max() < 2
    assert a.key.min() >= 0 and a.key.max() < 1 << 20
    assert not np.array_equal(a.key, b.key)
    again = traffic.open_loop(t, 2, 1, 1 << 20)
    assert np.array_equal(a.key, again.key)


def test_zipf_keys_are_skewed():
    t = {"key_distribution": "zipfian", "zipf_theta": 0.99}
    rng = np.random.default_rng(0)
    keys = traffic.draw_keys(rng, 200_000, 1 << 20, t)
    top = np.bincount(keys).max() / len(keys)
    assert 0.03 < top < 0.12       # rank 1 of 1M at theta 0.99: ~6.5%


def test_eq3_control_misses_in_bfloat16():
    sums_p = np.asarray([1500.0, 2000.0, 1800.0], np.float32)
    sums_q = np.asarray([2048.0, 2100.0, 2300.0], np.float32)
    full = ref.eq3_cpu(sums_p, sums_q, 256)
    low = ref.eq3_cpu(sums_p, sums_q, 256, "bfloat16")
    assert (full > 0.1).all() and (full < 0.99).all()
    assert ref.fp_rel_err(low, full).max() > 0.3
    assert ref.fp_rel_err(full, full).max() == 0.0


def test_reference_verdicts():
    q = np.asarray([2, 2, 2])
    p = np.asarray([[1, 2, 2], [2, 2, 2], [3, 2, 2], [3, 1, 2]])
    a, b, sp, sq = ref.order_host(p, q)
    codes = ref.verdicts(a, b)
    assert [ref.VERDICTS[c] for c in codes] == [
        "ancestor", "same", "descendant", "forked"]
    assert sp.tolist() == [5, 6, 7, 6] and sq.tolist() == [6] * 4


def test_result_line_keys():
    line = harness.result_line(
        True, 10, 0, {"sweep_ms": {"value": 1.5, "unit": "ms"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 1}, [("fp_max_rel_err", 0.001, 0.01)],
        breakdown={"device_ops": [], "idle_gaps": []})
    out = json.loads(line)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"]
    assert out["checks"]["fp_max_rel_err"] == {"value": 0.001,
                                               "limit": 0.01}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.SetupError):
        harness.peaks_for("TPU v9 imaginary")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_benchmark_entries_resolve_by_name():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        cfg = harness.load_config(bench, cell["config"])
        traffic.check_mix(harness.load_traffic(cell["traffic"]))
        assert harness.load_driver(cfg["driver"]).run
    for m in bench["per_layer"]:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fleet-sweep",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_benchmark_json_shape():
    import re
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    cells = {c["name"]: c for c in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and (ROOT / c["file"]).is_file()
    for c in cells.values():
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(c["name"]) and name.match(c["traffic"])
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for cell in cells:
        reported = {m["name"] for m in harness.cell_metrics(
            bench, cell, "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layer = harness.cell_metrics(bench, cell, "per_layer")
        assert layer and all(m["moves"] in reported for m in layer)
    assert set(e2e) >= {"setup_s"}
