#!/usr/bin/env python3
"""Readings a limit is set from: the program's and the control's.

    python3 bench/control.py --workload store-ycsb-c --seconds 51 \\
        --seeds 11,12,13

For each seed it runs the cell once, as ``bench/run.py`` does, then
compares what the window produced with the plain reference twice: as
the program produced it (the lower reading of each number) and with
the reference computed in bfloat16 in the program's place (the
control, whose smallest reading over the seeds is the upper one).  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.lib import harness  # noqa: E402

sys.path.insert(0, str(harness.SRC))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench.lib.trace import Profiler
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    cfg = harness.load_config(bench, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    driver = harness.load_driver(cfg["driver"])
    try:
        harness.require_chips(cell["chips"])
    except harness.SetupError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    events = harness.HostEvents()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = driver.run(cell, cfg, traffic, seed, args.seconds,
                         Profiler(False), time.perf_counter(), events)
        row = {"seed": seed, "e2e": out.e2e,
               "program": {n: v for n, v, _ in out.check("program")},
               "control": {n: v for n, v, _ in out.check("control")}}
        print("[control] " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
