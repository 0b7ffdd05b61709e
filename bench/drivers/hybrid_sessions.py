"""A serving replica's hybrid session store, swept closed loop.

Set-up draws the population from the seed: each session is a prefix
``v`` of the replica's local chain (uniform in ``[v_low,
local_events]``) and, for a seeded share, private events with ids drawn
from the seed.  The program builds its local chain of ``local_events``
events (``advance_local``), admits the sessions in chunks through
``HybridEngine.admit_many``, promotes the head of a seeded Zipf
permutation through ``promote`` and sweeps twice to warm up.  The
window is one caller: each sweep is ``classify()`` against the chain as
it stands, then one more local event (``advance_local(1)``), so no two
sweeps share a query clock.  A sweep ends with its ``HybridView`` on
the host.

``correct`` recomputes the first and last sweeps and a seeded draw over
the whole window once the engine is freed: hot rows against exact chain
containment (fp exactly 0), tail rows with the plain reference
(``reference.hybrid`` minting, ``reference.bloom`` order and Eq. 3) on
the device block by block, and every row against the exact truth for
false negatives.

Only a traced run attaches an observer; it hands the window's program
spans and counters to the readers.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench.lib import harness, history, openloop, traffic as traffic_lib
from bench.reference import bloom as ref
from bench.reference import hybrid as ref_h

#: sweeps compared with the reference: the first, the last and two drawn
#: from the seed over the rest of the window
COMPARED_SWEEPS = 4


class Population:
    """Sessions as catalog entries drawn from the seed, and the head of
    a Zipf permutation that is kept hot."""

    def __init__(self, cfg: dict, seed: int):
        n, P = cfg["sessions"], cfg["private_events"]
        rng = history.seed_rng(seed, 1)
        self.n, self.k, self.m = n, cfg["k"], cfg["m"]
        self.v = rng.integers(cfg["v_low"], cfg["local_events"] + 1, n)
        private = rng.random(n) < cfg["concurrent_fraction"]
        self.n_private = np.where(private, P, 0)
        self.offsets = np.concatenate([[0], np.cumsum(self.n_private)])
        self.ids = rng.integers(0, 1 << 32, (int(self.offsets[-1]), 2),
                                dtype=np.uint64).astype(np.int64)
        rank = history.seed_rng(seed, 2).permutation(n)
        self.hot = rank[:cfg["hot_capacity"]]
        w = np.arange(1, n + 1, dtype=np.float64) ** -cfg["zipf_theta"]
        self.hot_access_share = float(w[:len(self.hot)].sum() / w.sum())
        self.width = P * self.k

    def events(self, at: int, stop: int):
        o = self.offsets[at:stop + 1]
        return o - o[0], self.ids[o[0]:o[-1]]


def _verdicts_by_part(view) -> dict:
    code = ref.verdicts(view.p_le_q, view.q_le_p)
    return {part: {ref.VERDICTS[c]: int((code[sel] == c).sum())
                   for c in range(len(ref.VERDICTS))}
            for part, sel in (("hot", view.hot), ("tail", ~view.hot))}


def _orders(code: np.ndarray):
    """(p ≼ q, q ≼ p) that verdict codes claim."""
    same = code == ref.CODE["same"]
    return (same | (code == ref.CODE["ancestor"]),
            same | (code == ref.CODE["descendant"]))


def _tail_reference(pop: Population, prefix_dev, priv: np.ndarray,
                    rows: np.ndarray, V: int, chunk: int, dtype: str):
    """(status codes, sums, claimed fp) of sessions ``rows`` minted as
    bloom rows, against the chain at version ``V``, by the plain
    reference in ``dtype``."""
    le, ge, sums = [], [], []
    q = prefix_dev[V]
    for at in range(0, len(rows), chunk):
        sel = rows[at:at + chunk]
        cells = history.mint_on_device(prefix_dev[None], pop.v[sel, None],
                                       priv[sel])
        a, b, s = ref.order_device(cells, q, dtype)
        le.append(a)
        ge.append(b)
        sums.append(s)
    sp = np.concatenate(sums)
    sq = float(np.asarray(q, np.int64).sum())
    code = ref.verdicts(np.concatenate(le), np.concatenate(ge))
    return code, sp, ref.claimed_fp(code, sp, sq, pop.m, dtype)


def _claimed(view, sel, code) -> np.ndarray:
    """The program's fp of the direction each verdict claims."""
    fp = np.zeros(int(sel.sum()), np.float32)
    anc = code == ref.CODE["ancestor"]
    dsc = code == ref.CODE["descendant"]
    fp[anc] = view.fp_p_before_q[sel][anc]
    fp[dsc] = view.fp_q_before_p[sel][dsc]
    return fp


def run(cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float,
        profiler, t_start: float, events: harness.HostEvents,
        rate: float | None = None) -> harness.Outcome:
    from repro.hybrid import HybridConfig, HybridEngine
    from repro.obs import MetricsRecorder, Observer, Tracer

    if not hasattr(HybridEngine, "admit_many"):
        raise harness.SetupError("the program has no bulk admission "
                                 "(HybridEngine.admit_many)")
    traffic_lib.check_mix(traffic)
    if traffic["loop"] != "closed":
        raise harness.SetupError("the hybrid store is swept closed loop")
    per_call = traffic["ticks_between_calls"]
    pop = Population(cfg, seed)
    obs = (Observer(trace=Tracer(), metrics=MetricsRecorder())
           if profiler.enabled else None)
    eng = HybridEngine(HybridConfig(
        m=pop.m, k=pop.k, hot_capacity=cfg["hot_capacity"],
        tail_capacity=cfg["tail_capacity"], fp_budget=cfg["fp_budget"]),
        observer=obs)
    eng.advance_local(cfg["local_events"])
    step = cfg["load_chunk"]
    for at in range(0, pop.n, step):
        stop = min(at + step, pop.n)
        eng.admit_many(range(at, stop), pop.v[at:stop],
                       pop.events(at, stop))
    for sid in pop.hot.tolist():
        eng.promote(sid)
    # warm-up: two sweeps build the device mirror and compile everything
    # the window calls (the local chain stays where sweep 0 finds it)
    for _ in range(2):
        eng.classify()
    rng = history.seed_rng(seed, 50)
    n_drawn = COMPARED_SWEEPS - 2
    drawn: list = []
    kept: dict = {}
    annotate = openloop.annotator(profiler.enabled)
    rebuilds0 = eng.mirror_rebuilds
    n_spans0 = len(obs.trace.events()) if obs else 0
    readback0 = (obs.metrics.counter("hybrid_readback_bytes").value
                 if obs else 0)
    setup_s = time.perf_counter() - t_start
    mark = events.mark()
    profiler.start()
    sweeps = 0
    longest = 0.0
    t0 = t_prev = time.perf_counter()
    t_end = t0 + seconds
    with annotate("bench.window"):
        while True:
            V = eng.local_version
            with annotate("bench.sweep"):
                view = eng.classify()
                eng.advance_local(per_call)
            if sweeps == 0:
                kept[0] = (V, view)
            elif len(drawn) < n_drawn:
                drawn.append((sweeps, V, view))
            elif (j := int(rng.integers(0, sweeps))) < n_drawn:
                drawn[j] = (sweeps, V, view)
            sweeps += 1
            now = time.perf_counter()
            longest, t_prev = max(longest, now - t_prev), now
            if now >= t_end:
                break
    elapsed = time.perf_counter() - t0
    profiler.stop()
    kept.update({s: (V, v) for s, V, v in drawn})
    kept[sweeps - 1] = (V, view)
    ctx = {"sweeps": sweeps, "hot": int(view.hot.sum()),
           "tail": int((~view.hot).sum()), "m": eng.m}
    if obs:
        spans: dict = collections.defaultdict(float)
        for e in obs.trace.events()[n_spans0:]:
            spans[e["name"]] += e["dur_us"] / 1e6
        ctx["hybrid_spans"] = dict(spans)
        ctx["hybrid_readback_bytes"] = (
            obs.metrics.counter("hybrid_readback_bytes").value - readback0)
    info = {"sessions": pop.n, "hot": ctx["hot"], "tail": ctx["tail"],
            "m": eng.m, "sweeps": sweeps, "engine": view.engine,
            "hybrid_resizes": eng.resizes,
            "mirror_rebuilds_in_window": eng.mirror_rebuilds - rebuilds0,
            "hot_access_share": pop.hot_access_share,
            "longest_sweep_ms": longest * 1e3, **events.since(mark),
            "compared_sweeps": sorted(kept),
            "verdicts_sweep_0": _verdicts_by_part(kept[0][1])}
    V_max = eng.local_version
    del eng, view, drawn

    def run_check(variant: str = "program"):
        import jax.numpy as jnp
        chunk = cfg["reference_chunk"]
        prefix_dev = jnp.asarray(ref_h.prefix_cells(V_max, pop.k, pop.m))
        priv = ref_h.private_cells(pop.offsets, pop.ids, pop.k, pop.m,
                                   pop.width)
        low = "bfloat16" if variant == "control" else None
        checks = collections.Counter()
        fp_err = 0.0
        for _, (V, got) in sorted(kept.items()):
            idx = np.asarray(got.sids, np.int64)
            hot = got.hot
            seen = np.bincount(idx, minlength=pop.n)
            checks["rows_missing"] += int((seen != 1).sum())
            p_le_q, q_le_p = got.p_le_q, got.q_le_p
            sums = np.asarray(got.sum_p, np.float64)
            # the exact truth of every row; hot rows are held to it
            t_le, t_ge = ref_h.exact(pop.v[idx], pop.n_private[idx], V)
            want_hot = ref.verdicts(t_le[hot], t_ge[hot])
            want_sum = pop.k * (pop.v[idx] + pop.n_private[idx])[hot]
            code, sp, fp = _tail_reference(pop, prefix_dev, priv,
                                           idx[~hot], V, chunk, "float32")
            g_code = ref.verdicts(p_le_q[~hot], q_le_p[~hot])
            g_sum, g_fp = sums[~hot], _claimed(got, ~hot, g_code)
            g_hot = ref.verdicts(p_le_q[hot], q_le_p[hot])
            g_hot_sum = sums[hot]
            hot_fp = int(((got.fp_q_before_p[hot] != 0)
                          | (got.fp_p_before_q[hot] != 0)).sum())
            if low:
                g_code, g_sum, g_fp = _tail_reference(
                    pop, prefix_dev, priv, idx[~hot], V, chunk, low)
                g_hot = want_hot
                g_hot_sum = np.asarray(
                    jnp.asarray(want_sum, low).astype(jnp.float32),
                    np.float64)
                hot_fp = 0
            checks["status_mismatches"] += int((g_code != code).sum()
                                               + (g_hot != want_hot).sum())
            checks["sum_mismatches"] += int((g_sum != sp).sum()
                                            + (g_hot_sum != want_sum).sum())
            checks["hot_fp_nonzero"] += hot_fp
            # false negatives: a true order the answer does not claim
            a_le = np.empty(len(idx), bool)
            a_ge = np.empty(len(idx), bool)
            a_le[hot], a_ge[hot] = _orders(g_hot)
            a_le[~hot], a_ge[~hot] = _orders(g_code)
            checks["false_negatives"] += int(
                (t_le & ~a_le).sum() + (t_ge & ~a_ge).sum())
            fp_err = max(fp_err, float(ref.fp_rel_err(g_fp, fp).max()))
        names = ("status_mismatches", "sum_mismatches", "false_negatives",
                 "hot_fp_nonzero", "rows_missing")
        return [(name, checks[name], 0) for name in names] + [
            ("fp_max_rel_err", fp_err, cfg["limits"]["fp_max_rel_err"])]

    return harness.Outcome(
        attempted=sweeps, failed=0,
        e2e={"setup_s": setup_s, "sweep_ms": elapsed / sweeps * 1e3},
        ctx=ctx, info=info, check=run_check, setup_s=setup_s)
