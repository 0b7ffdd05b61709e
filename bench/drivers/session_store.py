"""One replica of a causally consistent session store, served open loop.

Set-up mints the session population from the seed (``lib.history``),
admits it in chunks through ``TieredRegistry.admit_many``, encodes the
window's update frames, and warms every shape the window uses.  The
window drives ``AdmissionPipeline.submit`` -> ticket ``result()``: a
query classifies the session's stored clock against the replica, an
update admits a new clock frame for the session when the replica has
seen everything it has.  The replica ticks on its own clock through the
pipeline's ``local_source``.

``correct`` compares every answered request with the plain reference
(``reference.bloom``) at the replica versions the request lived
through, holds related sessions to zero false negatives (the vector
truth), and reads back a seeded sample of acknowledged updates through
``TieredRegistry.get``.
"""
from __future__ import annotations

import math
import sys
import tempfile
import time

import numpy as np

from bench.lib import harness, history, openloop, traffic as traffic_lib
from bench.reference import bloom as ref

READBACK_SAMPLE = 2048


class SessionStore:
    """The deployment, set up once; ``serve`` runs one window on it."""

    def __init__(self, cfg: dict, seed: int, seconds: float, trace: bool,
                 policy_observer=None):
        import jax.numpy as jnp
        from repro.causal import CausalPolicy
        from repro.core import clock as bc
        from repro.serve.pipeline import AdmissionPipeline, PipelineConfig
        from repro.serve.tiers import TierConfig, TieredRegistry

        self.cfg, self.seed, self.trace = cfg, seed, trace
        m, k, W = cfg["m"], cfg["k"], cfg["writers"]
        self.m, self.k, self.W = m, k, W
        rep = cfg["replica"]
        self.tick_every = rep["tick_every_s"]
        max_ticks = int(harness.MAX_SECONDS / self.tick_every) + 2
        e0 = cfg["events_per_writer"]
        self.hist = history.History.make(
            m, k, W, e0 + math.ceil(max_ticks * rep["events_per_tick"] / W)
            + 1, seed)
        self.versions = history.replica_versions(
            W, e0, int(seconds / self.tick_every) + 1,
            rep["events_per_tick"])
        self.replica_cells = self.hist.cells(self.versions)

        # the population: version vectors up to ``lag_events`` per writer
        # behind the replica's, a share of them with private events
        n = cfg["sessions"]
        self.lag = cfg["lag_events"]
        rng = history.seed_rng(seed, 2)
        self.v = (e0 - rng.integers(0, self.lag + 1, (n, W))).astype(np.int16)
        self.conc = rng.random(n) < cfg["concurrent_fraction"]
        self.priv = history.private_cells(rng, n, cfg["private_events"], m,
                                          k, self.conc)
        self.sids = [f"s{i}" for i in range(n)]

        tc = cfg["tiers"]
        self._spill = tempfile.TemporaryDirectory(prefix="bench_cold_")
        self.policy = CausalPolicy(fp_threshold=cfg["fp_threshold"],
                                   observer=policy_observer)
        self.tiers = TieredRegistry(
            TierConfig(hot_capacity=tc["hot_capacity"],
                       warm_capacity=tc["warm_capacity"],
                       spill_dir=self._spill.name),
            m=m, k=k, policy=self.policy)
        cum = jnp.asarray(self.hist.cum)
        step = cfg["load_chunk"]
        zero = np.zeros((), np.int32)
        for at in range(0, n, step):
            cells = np.asarray(history.mint_on_device(
                cum, self.v[at:at + step], self.priv[at:at + step]))
            self.tiers.admit_many({
                self.sids[at + i]: bc.BloomClock(cells[i], zero, k)
                for i in range(cells.shape[0])})
        del cum

        self.replica = openloop.Replica([
            bc.BloomClock(jnp.asarray(c), jnp.zeros((), jnp.int32), k)
            for c in self.replica_cells])
        pc = cfg["pipeline"]
        self.pipe = AdmissionPipeline(
            self.tiers, self.replica.current,
            PipelineConfig(batch_size=pc["batch_size"],
                           queue_depth=pc["queue_depth"],
                           max_wait_s=pc["max_wait_s"],
                           digest_cache=pc["digest_cache"],
                           cache_capacity=pc["cache_capacity"]))

    # ---- clocks on the host ----
    def init_cells(self, idx) -> np.ndarray:
        idx = np.atleast_1d(idx)
        return self.hist.cells(self.v[idx].astype(np.int64), self.priv[idx])

    def frame_of(self, cells: np.ndarray) -> bytes:
        """§4 wire frame of one logical row: min lifted into the base,
        u8 residuals."""
        from repro.core import wire
        base = int(cells.min())
        resid = cells - base
        if int(resid.max()) > 255:
            raise ValueError("minted clock does not fit the u8 window")
        return wire.encode_clock({"cells": resid.astype(np.uint8),
                                  "base": base, "k": self.k})

    def make_updates(self, sched: traffic_lib.Schedule, stream: int):
        """Frames for the schedule's updates: version vectors up to
        ``lag_events`` per writer behind the replica's when due, a
        ``concurrent_fraction`` of them with private events (the gate
        rejects those it finds forked); every frame is distinct."""
        upd = np.flatnonzero(sched.update)
        rng = history.seed_rng(self.seed, stream)
        t_due = np.minimum((sched.due[upd] / self.tick_every).astype(int),
                           len(self.versions) - 1)
        top = self.versions[t_due]
        v = np.maximum(top - rng.integers(0, self.lag + 1, top.shape), 0)
        conc = rng.random(len(upd)) < self.cfg["concurrent_fraction"]
        priv = history.private_cells(rng, len(upd),
                                     self.cfg["private_events"], self.m,
                                     self.k, conc)
        cells = self.hist.cells(v, priv)
        frames = [None] * len(sched)
        row = np.full(len(sched), -1)
        for j, i in enumerate(upd):
            frames[i] = self.frame_of(cells[j])
            row[i] = j
        return frames, row, cells, conc

    # ---- warm-up ----
    def warm(self, traffic: dict) -> None:
        """Every shape the window uses: where the mix updates, each
        power-of-two bucket of the hot-slab write (rewriting stored rows
        unchanged); then a burst of the mix through the pipeline whose
        updates re-send stored clocks."""
        from repro.core import clock as bc
        b = 1
        while (traffic["update_fraction"] > 0
               and b <= self.cfg["pipeline"]["batch_size"]):
            idx = np.arange(b)
            cells = self.init_cells(idx)
            self.tiers.admit_many({self.sids[i]: bc.BloomClock(
                cells[j], np.zeros((), np.int32), self.k)
                for j, i in enumerate(idx)})
            b *= 2
        n = self.cfg["warmup_requests"]
        rate = traffic.get("rate_per_s", 1000.0)
        sched = traffic_lib.open_loop(traffic, n / rate, self.seed,
                                      len(self.sids), stream=20)
        frames = {i: self.frame_of(self.init_cells(sched.key[i])[0])
                  for i in np.flatnonzero(sched.update)}
        served = self._serve(sched, frames, n / rate)
        if served.n_answered != len(sched):
            raise RuntimeError("warm-up requests went unanswered")

    def _serve(self, sched, frames, seconds):
        pipe, sids = self.pipe, self.sids

        def submit(i):
            sid = sids[sched.key[i]]
            if sched.update[i]:
                return pipe.submit(sid, frame=frames[i])
            return pipe.submit(sid, kind="query")

        self.replica.version = 0
        return openloop.serve(sched.due, seconds, submit, self.replica,
                              self.tick_every, trace=self.trace)

    def serve(self, sched, frames, seconds):
        b0 = self.pipe.batches
        served = self._serve(sched, frames, seconds)
        return served, self.pipe.batches - b0

    # ---- read-back and teardown ----
    def read_back(self, sample: np.ndarray) -> np.ndarray:
        return np.stack([np.asarray(self.tiers.get(
            self.sids[i], count=False).logical_cells(), np.int64)
            for i in sample]) if len(sample) else np.zeros((0, self.m))

    def close(self) -> None:
        self.pipe.close()
        self.tiers.close()
        self._spill.cleanup()
        self.pipe = self.tiers = self.replica = None


def _code_of(name: str) -> int:
    return ref.CODE.get(name, -1)


def check(host: dict, sched, served: openloop.Served, upd_row, upd_cells,
          upd_conc, readback, limits: dict, variant: str = "program",
          updates_only: bool = False):
    """The comparison with the plain reference.  ``variant="control"``
    puts the reference computed in bfloat16 in the program's place;
    ``updates_only`` judges the update requests alone (their verdicts do
    not depend on what earlier windows stored)."""
    m, thr = host["m"], host["fp_threshold"]
    init_cells, rep_cells = host["init_cells"], host["replica_cells"]
    n = len(sched)
    answered = np.isfinite(served.done)
    if updates_only:
        answered &= sched.update
    got_code = np.full(n, -1, np.int8)
    got_fp = np.zeros(n, np.float64)
    got_adm = np.zeros(n, bool)
    for i in np.flatnonzero(answered):
        r = served.results[i]
        got_code[i], got_fp[i], got_adm[i] = (_code_of(r.verdict), r.fp,
                                              r.admitted)

    # candidate stored clocks of each request: ("u", row) an update
    # frame, ("i", key) the session's initial clock
    acks: dict = {}              # key -> [(done, row), ...]
    cands = []
    for i in range(n):
        key = int(sched.key[i])
        if sched.update[i]:
            cands.append([("u", int(upd_row[i]))])
            if answered[i] and got_adm[i]:
                acks.setdefault(key, []).append((served.done[i],
                                                 int(upd_row[i])))
            continue
        ts = served.sent[i]
        prior = acks.get(key, [])
        base = ("i", key)
        extra = []
        for done_t, row in prior:
            if done_t < ts:
                base = ("u", row)
            else:
                extra.append(("u", row))
        cands.append([base] + extra)

    # every (request, candidate, version) pair, evaluated at once
    pr, pc, pv = [], [], []
    for i in np.flatnonzero(answered):
        for c in cands[i]:
            for t in range(served.ver_sent[i], served.ver_done[i] + 1):
                pr.append(i)
                pc.append(c)
                pv.append(t)
    pr = np.asarray(pr, np.int64)
    pv = np.asarray(pv, np.int64)
    keys_i = np.asarray([c[1] for c in pc if c[0] == "i"], np.int64)
    rows = np.zeros((len(pc), m), np.int32)
    is_init = np.asarray([c[0] == "i" for c in pc], bool)
    if is_init.any():
        rows[is_init] = init_cells(keys_i)
    if (~is_init).any():
        rows[~is_init] = upd_cells[[c[1] for c in pc if c[0] == "u"]]
    p_le_q, q_le_p, sp, sq = ref.order_host(rows, rep_cells[pv])
    code = ref.verdicts(p_le_q, q_le_p)
    fp = ref.claimed_fp(code, sp, sq, m)
    gate_fp = np.where(code == ref.CODE["same"], 0.0,
                       ref.eq3_cpu(sp, sq, m))
    gate = p_le_q & (gate_fp <= thr)
    truth_related = np.asarray(
        [(not upd_conc[c[1]]) if c[0] == "u" else (not host["conc"][c[1]])
         for c in pc], bool)

    if variant == "control":
        # the reference in bfloat16 answers in the program's place, at
        # the replica version each request was sent under
        first = np.ones(len(pr), bool)
        first[1:] = pr[1:] != pr[:-1]
        sel = first
        got_code[pr[sel]] = code[sel]
        got_fp[pr[sel]] = ref.claimed_fp(code[sel], sp[sel], sq[sel], m,
                                         "bfloat16")
        got_adm[pr[sel]] = gate[sel]

    match = code == got_code[pr]
    match &= ~sched.update[pr] | (gate == got_adm[pr])
    err = np.where(match, ref.fp_rel_err(got_fp[pr], fp), np.inf)
    best = np.full(n, np.inf)
    np.minimum.at(best, pr, err)
    any_match = np.zeros(n, bool)
    np.logical_or.at(any_match, pr, match)
    related = np.ones(n, bool)
    np.logical_and.at(related, pr, truth_related)
    fn = answered & related & ~np.isin(got_code, (ref.CODE["ancestor"],
                                                  ref.CODE["same"]))
    fp_err = float(best[answered & any_match].max(initial=0.0))
    for i in np.flatnonzero(answered & ~any_match)[:5]:
        sel = pr == i
        print(f"mismatch: request {i} update={bool(sched.update[i])} "
              f"key={int(sched.key[i])} got={got_code[i]} "
              f"admitted={bool(got_adm[i])} fp={got_fp[i]!r} "
              f"versions={served.ver_sent[i]}..{served.ver_done[i]} "
              f"candidates={cands[i]} want={code[sel].tolist()} "
              f"gate={gate[sel].tolist()}", file=sys.stderr)

    # read-back: the last acknowledged update of each sampled session
    sample, stored = readback
    want = init_cells(sample).astype(np.int64)
    for j, key in enumerate(sample):
        if key in acks:
            want[j] = upd_cells[acks[key][-1][1]]
    if variant == "control":
        stored = want
    rb_bad = int((stored != want).any(axis=1).sum()) if len(sample) else 0

    return [
        ("unanswered", int(served.n_sent - served.n_answered), 0),
        ("verdict_mismatches", int((answered & ~any_match).sum()), 0),
        ("false_negatives", int(fn.sum()), 0),
        ("readback_mismatches", rb_bad, 0),
        ("fp_max_rel_err", fp_err, limits["fp_max_rel_err"]),
    ]


def readback_sample(seed: int, sched, served, n_items: int) -> np.ndarray:
    """Sessions to read back: acknowledged updates first, drawn from the
    seed, then a few that were never updated."""
    rng = history.seed_rng(seed, 30)
    acked = sorted({int(sched.key[i]) for i in range(len(sched))
                    if sched.update[i] and served.results[i] is not None
                    and served.results[i].admitted})
    pick = list(rng.permutation(acked)[:READBACK_SAMPLE])
    pick += list(rng.integers(0, n_items, READBACK_SAMPLE // 8))
    return np.asarray(pick, np.int64)


def run(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
        profiler, t_start: float, events: harness.HostEvents,
        rate: float | None = None) -> harness.Outcome:
    traffic_lib.check_mix(traffic)
    if traffic["loop"] != "open":
        raise harness.SetupError("the session store is served open loop")
    trace = profiler.enabled
    obs = None
    if trace:
        from repro.obs import Observer, Tracer
        obs = Observer(trace=Tracer())
    store = SessionStore(cfg, seed, seconds, trace, policy_observer=obs)
    store.warm(traffic)
    sched = traffic_lib.open_loop(traffic, seconds, seed, cfg["sessions"],
                                  rate=rate)
    frames, upd_row, upd_cells, upd_conc = store.make_updates(sched, 40)
    spans0 = len(obs.trace.events()) if obs else 0
    setup_s = time.perf_counter() - t_start
    mark = events.mark()
    profiler.start()
    served, batches = store.serve(sched, frames, seconds)
    profiler.stop()
    host_events = events.since(mark)
    spans = obs.trace.events()[spans0:] if obs else []
    sample = readback_sample(seed, sched, served, cfg["sessions"])
    stored = store.read_back(sample)
    occupancy = store.tiers.occupancy()
    host = {"m": store.m, "fp_threshold": cfg["fp_threshold"],
            "init_cells": store.init_cells, "conc": store.conc,
            "replica_cells": store.replica_cells}
    store.close()

    lat = served.latency_s()
    lag = served.lag_s()
    e2e = {"setup_s": setup_s}
    if len(lat):
        e2e["verdict_p99_ms"] = float(np.percentile(lat, 99)) * 1e3
    ctx = {"verdicts": served.n_answered, "batches": batches,
           "gen_lag_s": lag, "spans": spans}
    info = {"requests": len(sched), "sent": served.n_sent,
            "answered": served.n_answered,
            "updates": int(sched.update.sum()),
            "acknowledged_updates": int(sum(
                1 for r in served.results if r is not None
                and r.kind == "admit" and r.admitted)),
            "batches": batches, **host_events,
            "completed_per_s": served.completed_in_window() / seconds,
            "gen_lag_p99_ms": float(np.percentile(lag, 99)) * 1e3
            if len(lag) else None,
            "latency_p50_ms": float(np.percentile(lat, 50)) * 1e3
            if len(lat) else None,
            "tier_occupancy": occupancy,
            "replica_ticks": int(served.ver_done.max(initial=0))}

    def run_check(variant: str = "program"):
        return check(host, sched, served, upd_row, upd_cells, upd_conc,
                     (sample, stored), cfg["limits"], variant)

    return harness.Outcome(attempted=served.n_sent,
                           failed=served.n_sent - served.n_answered,
                           e2e=e2e, ctx=ctx, info=info, check=run_check,
                           setup_s=setup_s)
