#!/usr/bin/env python3
"""Knee sweep of an open-loop mix on a session-store configuration: one
set-up, then one window per offered rate, on the chip of this machine.

    python3 bench/sweep.py --config session-store --traffic ycsb-c \\
        --seed 7 --seconds 8 --rates 50,100,150,200,250

For each rate it prints the p50/p99 verdict latency (from the due
time), the generator's p99 lateness, the rate of verdicts completed
in the window, the backlog (median latency of the last quarter of the
requests over that of the first quarter) and the update verdicts that
disagree with the plain reference.  The knee is the highest rate whose
p99 stays within the limit with no growing backlog; the cell's mix file
then fixes its rate as a whole number.
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


def main(argv=None, *, config: dict | None = None,
         require_tpu: bool = True) -> int:
    """``config`` replaces the configuration file (tests pass small
    sizes); ``require_tpu=False`` skips the look for a chip."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a session-store configuration of BENCHMARK.json")
    ap.add_argument("--traffic", required=True,
                    help="an open-loop mix, bench/traffic/<name>.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, default=6)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    args = ap.parse_args(argv)

    import numpy as np
    from bench.drivers import session_store as ss
    from bench.lib import traffic as traffic_lib

    try:
        cfg = config or harness.load_config(harness.load_benchmark(),
                                            args.config)
        traffic = harness.load_traffic(args.traffic)
        if require_tpu:
            harness.require_chips(1)
    except harness.SetupError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    if require_tpu:
        harness.enable_compile_cache()
    events = harness.HostEvents()
    store = ss.SessionStore(cfg, args.seed, args.seconds, trace=False)
    store.warm(traffic)
    print(f"[setup] {time.perf_counter() - T_START:.1f} s", flush=True)
    ref_host = {"m": store.m, "fp_threshold": cfg["fp_threshold"],
                "init_cells": store.init_cells, "conc": store.conc,
                "replica_cells": store.replica_cells}
    rows = []
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        sched = traffic_lib.open_loop(traffic, args.seconds, args.seed,
                                      cfg["sessions"], stream=100 + n,
                                      rate=rate)
        frames, upd_row, upd_cells, upd_conc = store.make_updates(
            sched, 200 + n)
        mark = events.mark()
        served, batches = store.serve(sched, frames, args.seconds)
        host_events = events.since(mark)
        lat = served.latency_s() * 1e3
        q = max(1, len(lat) // 4)
        checks = ss.check(ref_host, sched, served, upd_row, upd_cells,
                          upd_conc, (np.zeros(0, np.int64),
                                     np.zeros((0, store.m))),
                          cfg["limits"], updates_only=True)
        row = {"rate": rate, "requests": len(sched),
               "answered": served.n_answered,
               "p50_ms": float(np.percentile(lat, 50)),
               "p99_ms": float(np.percentile(lat, 99)),
               "gen_lag_p99_ms": float(np.percentile(served.lag_s(), 99))
               * 1e3,
               "completed_per_s": served.completed_in_window()
               / args.seconds,
               "backlog_ratio": float(np.median(lat[-q:])
                                      / max(np.median(lat[:q]), 1e-9)),
               "rows_per_batch": served.n_answered / max(batches, 1),
               **host_events,
               "checks": {k: v for k, v, _ in checks}}
        rows.append(row)
        print("[rate] " + json.dumps(row), flush=True)
    store.close()
    events.close()
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "rates": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
