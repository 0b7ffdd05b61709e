"""A replica's registry of peer clocks, swept closed loop.

Set-up mints the peers from the seed around a base version of the
replica's history (a third each behind the base, ahead of every replica
version and concurrent, plus stragglers whose window outgrew a byte) and
admits them in chunks through ``ClockRegistry.admit_many``.  The window
is one caller running ``classify_all(local)`` back to back, each sweep
against the replica's next version, drawn from the seed within
``lag_events`` of the base: no two sweeps share a clock, and every sweep
sees the same mix.  A sweep ends with its ``FleetView`` on the host.

``correct`` recomputes the first and last sweeps and a seeded draw over
the whole window with the plain reference (``reference.bloom``) over the
same logical rows, made again on the device block by block once the
registry is freed: status and sums exactly, fp against Eq. 3 on the
host CPU.
"""
from __future__ import annotations

import time

import numpy as np

from bench.lib import harness, history, openloop, traffic as traffic_lib
from bench.reference import bloom as ref

#: sweeps compared with the reference: the first, the last and two drawn
#: from the seed over the rest of the window
COMPARED_SWEEPS = 4
STATUS_OF_CODE = {ref.CODE["ancestor"]: 0, ref.CODE["same"]: 1,
                  ref.CODE["descendant"]: 2, ref.CODE["forked"]: 3}


class Fleet:
    """Peers as version vectors plus private cells and stragglers, and
    the replica's versions, one per sweep."""

    def __init__(self, cfg: dict, seed: int):
        m, k, W = cfg["m"], cfg["k"], cfg["writers"]
        self.m, self.k, self.W = m, k, W
        e0, lag = cfg["events_per_writer"], cfg["lag_events"]
        # the chains reach as far as the farthest ahead peer
        self.hist = history.History.make(m, k, W, e0 + 2 * lag, seed)
        n = cfg["peers"]
        rng = history.seed_rng(seed, 2)
        kind = rng.integers(0, 3, n)      # behind, ahead, concurrent
        off = rng.integers(0, lag + 1, (n, W))
        self.v = np.where(kind[:, None] == 1, e0 + lag + off, e0 - off
                          ).astype(np.int16)
        self.priv = history.private_cells(rng, n, cfg["private_events"], m,
                                          k, kind == 2)
        rows = rng.choice(n, cfg["stragglers"], replace=False)
        self.burst = (np.sort(rows), rng.integers(0, m, len(rows)),
                      cfg["straggler_burst"])
        # every replica version lies between the behind and the ahead
        # peers: up to lag - 1 events per writer past the base
        self.versions = e0 + history.seed_rng(seed, 3).integers(
            0, lag, (cfg["replica"]["versions"], W))
        self.n = n

    def rows_on_device(self, cum_dev, at: int, stop: int):
        """Logical rows [at, stop) made on the device."""
        import jax.numpy as jnp
        cells = history.mint_on_device(cum_dev, self.v[at:stop],
                                       self.priv[at:stop])
        rows, cols, burst = self.burst
        sel = (rows >= at) & (rows < stop)
        if sel.any():
            cells = cells.at[jnp.asarray(rows[sel] - at),
                             jnp.asarray(cols[sel])].add(burst)
        return cells


def _reference(fleet: Fleet, cum_dev, q_dev, ts: list, chunk: int,
               dtype: str) -> dict:
    """{t: (status, sums, claimed fp)} of every peer against the replica
    at each version in ``ts``, by the plain reference in ``dtype``; each
    block of rows is made once."""
    le = {t: [] for t in ts}
    ge = {t: [] for t in ts}
    sums = {t: [] for t in ts}
    for at in range(0, fleet.n, chunk):
        rows = fleet.rows_on_device(cum_dev, at, min(at + chunk, fleet.n))
        for t in ts:
            a, b, s = ref.order_device(rows, q_dev[t], dtype)
            le[t].append(a)
            ge[t].append(b)
            sums[t].append(s)
    out = {}
    for t in ts:
        sp = np.concatenate(sums[t])
        sq = float(np.asarray(q_dev[t], np.int64).sum())
        code = ref.verdicts(np.concatenate(le[t]), np.concatenate(ge[t]))
        status = np.vectorize(STATUS_OF_CODE.get, otypes=[np.int8])(code)
        out[t] = (status, sp, ref.claimed_fp(code, sp, sq, fleet.m, dtype))
    return out


def run(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
        profiler, t_start: float, events: harness.HostEvents,
        rate: float | None = None) -> harness.Outcome:
    import jax.numpy as jnp
    from repro.causal import CausalPolicy
    from repro.core import clock as bc
    from repro.fleet import ClockRegistry

    traffic_lib.check_mix(traffic)
    if traffic["loop"] != "closed":
        raise harness.SetupError("the fleet registry is swept closed loop")
    per_call = traffic["ticks_between_calls"]
    fleet = Fleet(cfg, seed)
    reg = ClockRegistry(capacity=cfg["capacity"], m=fleet.m, k=fleet.k,
                        policy=CausalPolicy())
    cum = jnp.asarray(fleet.hist.cum)
    zero = np.zeros((), np.int32)
    step = cfg["load_chunk"]
    for at in range(0, fleet.n, step):
        cells = np.asarray(fleet.rows_on_device(cum, at,
                                                min(at + step, fleet.n)))
        reg.admit_many({f"p{at + i}": bc.BloomClock(cells[i], zero, fleet.k)
                        for i in range(cells.shape[0])})
    q_dev = jnp.asarray(fleet.hist.cells(fleet.versions))
    n_versions = len(fleet.versions)

    def local(t):
        return bc.BloomClock(q_dev[t % n_versions], jnp.zeros((), jnp.int32),
                             fleet.k)

    # warm-up: two sweeps compile and load everything the window calls
    for t in range(2):
        reg.classify_all(local(n_versions - 1 - t))
    # sweeps compared: the first, the last, and a seeded reservoir over
    # the rest, so the draw spans the whole window
    rng = history.seed_rng(seed, 50)
    n_drawn = COMPARED_SWEEPS - 2
    drawn: list = []
    kept: dict = {}
    annotate = openloop.annotator(profiler.enabled)
    setup_s = time.perf_counter() - t_start
    mark = events.mark()
    profiler.start()
    sweeps = 0
    longest = 0.0
    t0 = t_prev = time.perf_counter()
    t_end = t0 + seconds
    with annotate("bench.window"):
        while True:
            t = sweeps * per_call
            with annotate("bench.sweep"):
                view = reg.classify_all(local(t))
            if sweeps == 0:
                kept[0] = (t, view)
            elif len(drawn) < n_drawn:
                drawn.append((sweeps, t, view))
            elif (j := int(rng.integers(0, sweeps))) < n_drawn:
                drawn[j] = (sweeps, t, view)
            sweeps += 1
            now = time.perf_counter()
            longest, t_prev = max(longest, now - t_prev), now
            if now >= t_end:
                break
    elapsed = time.perf_counter() - t0
    profiler.stop()
    kept.update({s: (t, v) for s, t, v in drawn})
    kept[sweeps - 1] = ((sweeps - 1) * per_call, view)
    info = {"peers": fleet.n, "m": fleet.m, "sweeps": sweeps,
            "engine": view.engine, "packed": reg.packed,
            "longest_sweep_ms": longest * 1e3, **events.since(mark),
            "replica_versions_wrapped": (sweeps - 1) * per_call
            >= n_versions,
            "compared_sweeps": sorted(kept),
            "verdicts_last_sweep": view.counts()}
    del reg, view, drawn

    def run_check(variant: str = "program"):
        chunk = cfg["reference_chunk"]
        checks = {"status_mismatches": 0, "sum_mismatches": 0,
                  "fp_max_rel_err": 0.0}
        ts = sorted({t % n_versions for t, _ in kept.values()})
        want = _reference(fleet, cum, q_dev, ts, chunk, "float32")
        low = (_reference(fleet, cum, q_dev, ts, chunk, "bfloat16")
               if variant == "control" else None)
        for _, (t, got) in sorted(kept.items()):
            t %= n_versions
            status, sums, fp = want[t]
            g_status, g_sums, g_fp = got.status, got.sums, got.fp
            if variant == "control":
                g_status, g_sums, g_fp = low[t]
            checks["status_mismatches"] += int((g_status != status).sum()
                                               + (~got.alive).sum())
            checks["sum_mismatches"] += int((np.asarray(g_sums, np.float64)
                                             != sums).sum())
            checks["fp_max_rel_err"] = max(
                checks["fp_max_rel_err"],
                float(ref.fp_rel_err(g_fp, fp).max()))
        return [("status_mismatches", checks["status_mismatches"], 0),
                ("sum_mismatches", checks["sum_mismatches"], 0),
                ("fp_max_rel_err", checks["fp_max_rel_err"],
                 cfg["limits"]["fp_max_rel_err"])]

    ctx = {"sweeps": sweeps, "rows": cfg["capacity"], "m": fleet.m}
    return harness.Outcome(
        attempted=sweeps, failed=0,
        e2e={"setup_s": setup_s, "sweep_ms": elapsed / sweeps * 1e3},
        ctx=ctx, info=info, check=run_check, setup_s=setup_s)

