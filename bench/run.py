#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (loading, warm-up, compiling) is timed as ``setup_s``; then the
window runs for ``--seconds``; then what the window produced is
compared with the plain reference.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics from a profiler trace of the window), ``device`` and, when
traced, ``breakdown``; then ``checks``, each number compared beside its
limit.  The same numbers close standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.lib import harness  # noqa: E402

sys.path.insert(0, str(harness.SRC))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, config: dict | None = None,
             rate: float | None = None, variant: str = "program",
             root: Path = harness.ROOT):
    """Everything but the printing: returns (result dict, checks, info).
    ``config`` replaces the cell's configuration file (tests pass small
    sizes); ``require_tpu=False`` skips the look for a chip;
    ``variant="control"`` judges the bfloat16 reference in the program's
    place (the benchmark's own runs never do)."""
    import jax
    if not 1 <= seconds <= harness.MAX_SECONDS:
        raise harness.SetupError(
            f"--seconds must be 1..{harness.MAX_SECONDS}")
    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, name)
    cfg = config if config is not None else harness.load_config(
        bench, cell["config"], root)
    traffic = harness.load_traffic(cell["traffic"], root)
    driver = harness.load_driver(cfg["driver"])
    devices = (harness.require_chips(cell["chips"]) if require_tpu
               else jax.devices())
    if require_tpu:
        harness.enable_compile_cache()
    events = harness.HostEvents()

    from bench.lib.trace import Profiler
    profiler = Profiler(trace)
    try:
        out = driver.run(cell, cfg, traffic, seed, seconds, profiler,
                         T_START, events, rate=rate)
    finally:
        events.close()
    device = harness.device_info(devices, cell["chips"])
    checks = out.check(variant)
    interpreted = harness.interpreted_kernels() if require_tpu else []
    checks.append(("interpreted_kernels", len(interpreted), 0))
    breakdown = None
    if trace:
        summ = profiler.summary
        ctx = dict(out.ctx, trace=summ, device=device,
                   peaks=harness.peaks_for(device["kind"])
                   if require_tpu else None)
        metrics = harness.read_per_layer(
            harness.cell_metrics(bench, name, "per_layer"), ctx, root)
        device["busy_s"] = summ.busy_s()
        device["window_s"] = summ.window_s
        breakdown = summ.breakdown()
        mods: dict = {}
        for o in summ.ops:
            key = f"{o.name} [{o.module}]"
            mods[key] = mods.get(key, 0.0) + (o.end_ns - o.start_ns) / 1e9
        out.info["trace_ops_by_module"] = sorted(
            mods.items(), key=lambda kv: -kv[1])[:12]
    else:
        metrics = {m["name"]: {"value": harness.finite(out.e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in harness.cell_metrics(bench, name, "end_to_end")}
    info = dict(out.info, setup_s=out.setup_s,
                compiles_total=events.count,
                compile_s_total=events.seconds,
                interpreted=interpreted)
    result = {"correct": harness.passed(checks), "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result, checks, info


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result, checks, info = run_cell(args.workload, args.seed,
                                        args.seconds, bool(args.trace))
    except harness.SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print("[info] " + json.dumps(info, default=str), flush=True)
    harness.print_checks(checks)
    print(harness.result_line(
        result["correct"], result["attempted"], result["failed"],
        result["metrics"], result["device"], checks,
        result.get("breakdown")), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
