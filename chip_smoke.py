#!/usr/bin/env python3
"""Bring-up smoke run of the served causality path on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded path only

Each phase drives the system through its normal entry points on seeded
data at real widths, then checks what came out against the plain
references: ``core.clock.ordering`` / ``fp_rate`` for bloom verdicts,
``kernels.ref`` for the tick and merge kernels, the exact chain
containment for hybrid hot rows, and the churn driver's own vector
truth for the served path.  Flags and sums must match exactly.  Eq. 3
fp is recomputed on the host CPU from the reference sums and must agree
within ``FP_RTOL``, since the chip's exp/log differ from the CPU's.

One chip:
  ovm      one-vs-many packed classify over a device-resident
           1,048,576 x m=256 u8 slab (``CausalEngine.classify``)
  pairs    fleet acceptance all-pairs at n = m = 1024, every engine the
           TPU dispatch can pick (``CausalEngine.pairs``)
  hybrid   4,096 exact hot rows over a 65,536 x m=512 packed tail
           (``HybridEngine.classify``)
  runtime  ``ClockRuntime`` lineage / admit_merge (fused merge_compare)
           and the batched Pallas tick (``kernels.ops.tick``)
  serve    ``serve.churn.run_churn`` at the churn's default geometry,
           enough arrivals that hot, warm and cold tiers all hold sessions
  model    ``ServingEngine`` at the full qwen1.5-0.5b config: batch 4,
           prompt 32, 16 greedy tokens twice, clock-gated adopt_many

Four chips (``--chips 4``): a ``make_fleet_mesh(4)`` registry's
classify_all and ring all_pairs, and one mesh-transport gossip session,
each bit-identical to the same call on a single-device registry.

The script needs a TPU: without one it exits non-zero before any phase.
It also fails if any kernel on the path ran in the Pallas interpreter.
It runs in one process (a chip belongs to one process at a time).  The
last line of standard output is one JSON object, printed only when
every phase passed.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Relative tolerance of the chip's Eq. 3 fp against the host CPU's.
# fp = exp(ΣA·log(inner)) turns an absolute error in the float32 log
# into a relative error ΣA times as large.  On TPU v5e the log of
# values near 1 is off by up to about 2e-6 (the CPU's by 2e-9), so at
# the hybrid phase's ΣA ≈ 2,400 the two fp differ by about 5e-3.
FP_RTOL = 1e-2
REF_CHUNK = 131072      # reference rows per device call


# ---------------------------------------------------------------------------
# timing
# ---------------------------------------------------------------------------

_COMPILE_S = [0.0]


def _listen_compiles():
    import jax
    from jax._src.dispatch import BACKEND_COMPILE_EVENT

    def on_event(event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            _COMPILE_S[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)


def _timed(fn):
    """(result, seconds) with the result materialized on the device."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def _same(name, got, want):
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}"
    bad = int(np.count_nonzero(got != want))
    assert bad == 0, f"{name}: {bad} of {want.size} differ"


def _cpu_fp(sum_a, sum_b, m):
    """Eq. 3 (``core.clock.fp_rate``) evaluated on the host CPU, so the
    chip's fp is held to the CPU's exp/log and not to its own."""
    import jax
    import numpy as np
    from repro.core import clock as bc
    cpu = jax.devices("cpu")[0]
    sum_a, sum_b = (jax.device_put(np.asarray(x, np.float32), cpu)
                    for x in (sum_a, sum_b))
    return np.asarray(jax.jit(bc.fp_rate, static_argnums=2)(sum_a, sum_b, m))


def _close_fp(name, got, sum_a, sum_b, m):
    """Check the chip's fp of "A before B" against the CPU's Eq. 3 on the
    exact sums; return the largest relative error."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = _cpu_fp(sum_a, sum_b, m).astype(np.float64)
    assert got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}"
    assert np.isfinite(got).all(), f"{name}: non-finite values"
    err = np.abs(got - want)
    # the chip flushes float32 subnormals to zero
    tiny = np.finfo(np.float32).tiny
    bad = int(np.count_nonzero(err > FP_RTOL * np.abs(want) + tiny))
    big = np.abs(want) >= tiny
    rel = float((err[big] / np.abs(want[big])).max()) if big.any() else 0.0
    assert bad == 0, (f"{name}: {bad} of {want.size} outside rtol {FP_RTOL} "
                      f"(max rel err {rel:.3g}, max abs err {err.max():.3g})")
    return rel


def _verdicts(le, ge):
    """Counts of (equal, after, before, concurrent) from two flag arrays."""
    import numpy as np
    le, ge = np.asarray(le, bool), np.asarray(ge, bool)
    return {"equal": int((le & ge).sum()), "q<p": int((le & ~ge).sum()),
            "p<q": int((~le & ge).sum()), "concurrent": int((~le & ~ge).sum())}


@functools.lru_cache(maxsize=None)
def _ref_fn(k: int):
    """Jitted plain reference: ``core.clock.ordering`` flags of one query
    (or a block of rows) against a block of logical clocks, and both
    clocks' sums, broadcast to the flags' shape."""
    import jax
    import jax.numpy as jnp
    from repro.core import clock as bc

    def ref(a, b):
        ca = bc.BloomClock(a, jnp.zeros(a.shape[:-1], jnp.int32), k)
        cb = bc.BloomClock(b, jnp.zeros(b.shape[:-1], jnp.int32), k)
        o = bc.ordering(ca, cb)
        shape = o.a_le_b.shape
        return (o.a_le_b, o.b_le_a,
                jnp.broadcast_to(bc.clock_sum(ca), shape),
                jnp.broadcast_to(bc.clock_sum(cb), shape))

    return jax.jit(ref)


def _ref_one_vs_many(q, logical, k):
    """Reference (q <= p, p <= q, sum q, sum p) of query ``q`` [m] vs
    ``logical`` [N, m], computed in row chunks on the device."""
    import jax
    import numpy as np
    parts = [jax.device_get(_ref_fn(k)(q, logical[i:i + REF_CHUNK]))
             for i in range(0, logical.shape[0], REF_CHUNK)]
    return [np.concatenate([p[j] for p in parts]) for j in range(4)]


# ---------------------------------------------------------------------------
# data (seeded, made on the device in bulk)
# ---------------------------------------------------------------------------

def _related_rows(key, q, n):
    """[n, m] int32 logical clocks around query ``q``: a quarter equal,
    a quarter ahead (q ≼ p), a quarter behind (p ≼ q), a quarter
    concurrent; every row's span stays far inside a byte."""
    import jax
    import jax.numpy as jnp
    m = q.shape[0]
    k_cls, k_a, k_b, k_ma, k_mb = jax.random.split(key, 5)
    cls = jax.random.randint(k_cls, (n, 1), 0, 4)
    inc_a = (jax.random.bernoulli(k_ma, 0.05, (n, m))
             * jax.random.randint(k_a, (n, m), 1, 12)).astype(jnp.int32)
    inc_b = (jax.random.bernoulli(k_mb, 0.05, (n, m))
             * jax.random.randint(k_b, (n, m), 1, 12)).astype(jnp.int32)
    delta = jnp.where(cls == 0, 0,
                      jnp.where(cls == 1, inc_a,
                                jnp.where(cls == 2, -inc_a, inc_a - inc_b)))
    return q[None, :] + delta


def _pack(logical):
    """§4 packing on the device: per-row min base + u8 residuals."""
    import jax.numpy as jnp
    base = jnp.min(logical, axis=1)
    return (logical - base[:, None]).astype(jnp.uint8), base.astype(jnp.int32)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_ovm() -> dict:
    import jax
    import jax.numpy as jnp
    from repro.causal import CausalEngine, PackedSlab

    rows, m, k = 1 << 20, 256, 4
    key = jax.random.PRNGKey(11)
    q = jax.random.randint(key, (m,), 100, 140, jnp.int32)
    logical = jax.jit(_related_rows, static_argnums=2)(
        jax.random.fold_in(key, 1), q, rows)
    u8, base = jax.jit(_pack)(logical)
    slab = PackedSlab(u8, base)
    engine = CausalEngine()
    res, first = _timed(lambda: engine.classify(q, slab))
    _, warm = _timed(lambda: engine.classify(q, slab))
    got = jax.device_get(res)
    le, ge, sq, sp = _ref_one_vs_many(q, logical, k)
    _same("q_le_p", got.q_le_p, le)
    _same("p_le_q", got.p_le_q, ge)
    _same("sum_p", got.sum_p, sp)
    rel = max(_close_fp("fp_q_before_p", got.fp_q_before_p, sq, sp, m),
              _close_fp("fp_p_before_q", got.fp_p_before_q, sp, sq, m))
    return {"rows": rows, "m": m,
            "slab_MiB": u8.nbytes / 2**20, "engine": res.engine,
            "blocks": dict(res.blocks or ()), "first_call_s": first,
            "warm_call_s": warm, "fp_max_rel_err": rel,
            **_verdicts(got.q_le_p, got.p_le_q)}


def _fleet_clocks(n, m, seed):
    """Causal-DAG fleet: each clock extends a random earlier one by a
    few sparse increments, so ancestry and concurrency both occur; the
    value span stays <= 64 (every engine, the thermometer included)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    cells = np.zeros((n, m), np.int64)
    for i in range(1, n):
        parent = cells[rng.integers(0, i)] if rng.random() < 0.9 else 0
        inc = (rng.random(m) < 0.02) * rng.integers(1, 3, m)
        cells[i] = parent + inc
    cells = np.minimum(cells, 60) + 1000          # shared window offset
    return cells.astype(np.int32)


def phase_pairs() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.causal import CausalEngine, CausalPolicy, PackedSlab
    from repro.kernels import pack

    n, m, k = 1024, 1024, 4
    cells = jnp.asarray(_fleet_clocks(n, m, 7))
    u8, base, ok = pack.pack_rows(cells)
    assert bool(ok.all())
    slab = PackedSlab(u8, base)                 # per-row (non-uniform) bases
    ref = [np.concatenate(x) for x in zip(*[
        jax.device_get(_ref_fn(k)(cells[i:i + 128, None, :],
                                  cells[None, :, :]))
        for i in range(0, n, 128)])]
    le_ref, ge_ref, sa, sb = ref
    runs = {
        "tri(packed slab)": lambda: CausalEngine().pairs(slab),
        "tri(i32 packed on the fly)": lambda: CausalEngine().pairs(cells),
        "full": lambda: CausalEngine().pairs(slab, engine="full"),
        "mxu": lambda: CausalEngine().pairs(slab, engine="mxu"),
        "i32": lambda: CausalEngine(
            CausalPolicy(engine="i32", pack=False)).pairs(cells),
    }
    out = {"n": n, "m": m}
    rel = 0.0
    for name, fn in runs.items():
        res, first = _timed(fn)
        _, warm = _timed(fn)
        got = jax.device_get(res)
        _same(f"{name} a_le_b", got["a_le_b"], le_ref)
        _same(f"{name} b_le_a", got["b_le_a"], ge_ref)
        rel = max(rel, _close_fp(f"{name} fp", got["fp"], sa, sb, m))
        out[name] = {"engine": res.engine, "first_call_s": first,
                     "warm_call_s": warm}
    out["fp_max_rel_err"] = rel
    out.update(_verdicts(le_ref, ge_ref))
    return out


def phase_hybrid() -> dict:
    import jax.numpy as jnp
    import numpy as np
    from repro.hybrid import HybridConfig, HybridEngine

    H, tail, m, k = 4096, 65536, 512, 4
    rng = np.random.default_rng(5)
    n = H + tail
    hyb = HybridEngine(HybridConfig(
        m=m, k=k, hot_capacity=H, tail_capacity=n))
    hyb.advance_local(600)
    V = hyb.local_version
    vs = rng.integers(0, V + 1, n)
    # Every verdict occurs in both the hot and the tail part: a session
    # at version v has seen the local chain's first v events, and a
    # private one also holds two events of its own.
    perm = rng.permutation(n)
    private = np.zeros(n, bool)
    for part in (perm[:H], perm[H:]):
        vs[part[:32]] = V                               # equal
        vs[part[32:160]] = V                            # q<p: local ≼ peer
        private[part[32:160]] = True
        vs[part[160:288]] = rng.integers(0, V, 128)     # concurrent
        private[part[160:288]] = True
    t0 = time.perf_counter()
    for i in range(n):
        events = ((0xC0FFEE, 2 * i), (0xC0FFEE, 2 * i + 1)) \
            if private[i] else ()
        hyb.admit(f"h{i}", int(vs[i]), events)
    for i in perm[:H]:
        hyb.promote(f"h{i}")
    setup = time.perf_counter() - t0
    view, first = _timed(hyb.classify)
    _, warm = _timed(hyb.classify)
    idx = np.asarray([int(s[1:]) for s in view.sids])
    v, npriv = vs[idx], private[idx]
    assert int(view.hot.sum()) == H and view.hot[:H].all(), view.hot.sum()
    # hot rows: exact chain containment, fp identically zero
    _same("hot q_le_p", view.q_le_p[:H], V <= v[:H])
    _same("hot p_le_q", view.p_le_q[:H], (v[:H] <= V) & ~npriv[:H])
    assert not view.fp_q_before_p[:H].any() and \
        not view.fp_p_before_q[:H].any(), "hot fp must be 0"
    # tail rows: the plain bloom reference on the same logical cells
    slab = hyb.slab()
    assert not slab.wide
    logical = slab.cells_u8.astype(jnp.int32) + slab.base[:, None]
    le, ge, sq, sp = _ref_one_vs_many(
        hyb.local_clock().logical_cells(), logical, k)
    _same("tail q_le_p", view.q_le_p[H:], le)
    _same("tail p_le_q", view.p_le_q[H:], ge)
    _same("tail sum_p", view.sum_p[H:], sp)
    rel = max(
        _close_fp("tail fp_q_before_p", view.fp_q_before_p[H:], sq, sp, m),
        _close_fp("tail fp_p_before_q", view.fp_p_before_q[H:], sp, sq, m))
    # the §3 guarantee against the exact truth: no related session is
    # ever reported concurrent
    related = ~npriv & (v <= V)
    fn = int((related & ~view.p_le_q).sum())
    assert fn == 0, f"{fn} false negatives"
    hot = _verdicts(view.q_le_p[:H], view.p_le_q[:H])
    tail_v = _verdicts(view.q_le_p[H:], view.p_le_q[H:])
    for part, counts in (("hot", hot), ("tail", tail_v)):
        assert all(counts.values()), f"{part} lacks a verdict: {counts}"
    return {"hot": H, "tail": len(view.sids) - H, "m": m,
            "engine": view.engine, "setup_s": setup, "first_call_s": first,
            "warm_call_s": warm, "false_negatives": fn,
            "fp_max_rel_err": rel, "hot_verdicts": hot,
            "tail_verdicts": tail_v}


def phase_runtime() -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import clock as bc
    from repro.core.hashing import bloom_indices
    from repro.kernels import ops, ref
    from repro.runtime.clock_runtime import ClockConfig, ClockRuntime

    B, m, E = 1024, 1024, 16
    cfg = ClockConfig(m=m, k=4)

    def runtime(run_id, steps):
        rt = ClockRuntime(cfg, run_id=run_id)
        for s in steps:
            rt.tick_step(s)
        return rt

    local = runtime("node", range(64))
    peers = {"ancestor": runtime("node", range(40)).clock,
             "same": runtime("node", range(64)).clock,
             "descendant": runtime("node", range(80)).clock,
             "forked": runtime("other", range(64)).clock}
    statuses = {}
    for name, peer in peers.items():
        status, fp = local.lineage(peer)
        o = bc.ordering(peer, local.clock)
        want = ("same" if bool(o.a_le_b) and bool(o.b_le_a)
                else "ancestor" if bool(o.a_le_b)
                else "descendant" if bool(o.b_le_a) else "forked")
        assert status == want == name, (name, status, want)
        statuses[name] = status
    merged_ref = bc.merge(local.clock, peers["descendant"])
    ok, status, _ = local.admit_merge(peers["descendant"])
    assert ok and status == "descendant", (ok, status)
    _same("admit_merge cells", local.clock.logical_cells(),
          merged_ref.logical_cells())

    # fused merge_compare and the Pallas tick at a batch
    key = jax.random.PRNGKey(3)
    a = jax.random.randint(key, (B, m), 0, 50, jnp.int32)
    b = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5,
                                       (B, 1)), a + 1, a - 1)
    b = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 2), 0.01,
                                       (B, m)), 0, b)
    got, mc_first = _timed(lambda: ops.merge_compare(a, b))
    merged, flags, sums, _ = ref.bloom_merge_compare_ref(a, b)
    _same("merged", got["merged"], merged)
    _same("a_le_b", got["a_le_b"], flags[:, 0].astype(bool))
    _same("b_le_a", got["b_le_a"], flags[:, 1].astype(bool))
    _same("sum_a", got["sum_a"], sums[:, 0])
    rel = _close_fp("fp_a_before_b", got["fp_a_before_b"], sums[:, 0],
                    sums[:, 1], m)
    hi = jax.random.randint(key, (B, E), 0, 2**31 - 1).astype(jnp.uint32)
    lo = jnp.arange(B * E, dtype=jnp.uint32).reshape(B, E)
    ticked, tick_first = _timed(lambda: ops.tick(a, hi, lo, k=4))
    probes = bloom_indices(hi, lo, 4, m).reshape(B, -1).astype(jnp.int32)
    _same("tick", ticked, ref.bloom_tick_ref(a, probes))
    return {"statuses": statuses, "merge_compare_batch": B, "m": m,
            "merge_compare_first_call_s": mc_first,
            "tick_first_call_s": tick_first, "tick_events": E,
            "fp_max_rel_err": rel,
            **_verdicts(got["a_le_b"], got["b_le_a"])}


def phase_serve() -> dict:
    from repro.serve.churn import ChurnConfig, run_churn

    cfg = ChurnConfig(sessions=131072, steps=8, queries_per_step=512)
    report = run_churn(cfg)
    tiers = report.tier_counts
    assert report.fn_violations == 0, f"{report.fn_violations} false negatives"
    assert report.ok()
    assert all(tiers.get(t, 0) > 0 for t in ("hot", "warm", "cold")), tiers
    return {"sessions": report.sessions, "queries": report.queries,
            "m": cfg.m, "k": cfg.k, "batch": cfg.batch_size,
            "tiers": tiers, "admitted": report.admitted,
            "rejected": report.rejected, "migrations": report.migrations,
            "expiries": report.expiries,
            "false_negatives": report.fn_violations,
            "concurrent_seen": report.concurrent_seen,
            "measured_fp": report.measured_fp,
            "claimed_fp_mean": report.claimed_fp_mean,
            "cache_hits": report.cache_hits,
            "cache_misses": report.cache_misses,
            "promotions": report.promotions, "demotions": report.demotions,
            "spills": report.spills, "churn_s": report.wall_s}


def phase_model() -> dict:
    import jax
    import numpy as np
    from repro.causal import CausalPolicy
    from repro.configs import get_config
    from repro.core import clock as bc
    from repro.models.params import init_params
    from repro.runtime.clock_runtime import ClockConfig
    from repro.serving.engine import ServeConfig, ServingEngine

    batch, prompt_len, gen = 4, 32, 16
    cfg = get_config("qwen1_5_0_5b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    s_cfg = ServeConfig(max_batch=batch, max_seq=prompt_len + gen + 8)
    c_cfg = ClockConfig(policy=CausalPolicy(fp_threshold=1.0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                                 0, cfg.vocab)
    engine = ServingEngine(params, cfg, s_cfg, c_cfg, replica_id="A")
    runs = []
    for _ in range(2):
        t0 = time.perf_counter()
        session = engine.admit(prompts)
        toks = np.asarray(engine.generate(session, gen))
        runs.append((toks, time.perf_counter() - t0))
        logits = np.asarray(session["last_logits"], np.float32)
        assert np.isfinite(logits).all(), "non-finite logits"
    _same("greedy tokens, run 2 vs run 1", runs[1][0], runs[0][0])
    assert runs[0][0].shape == (batch, gen)

    # clock-gated migration: B gossiped A's clock (accept), C has its
    # own history (refuse); verdicts vs the plain reference
    verdicts = {}
    for rid, share in (("B", True), ("C", False)):
        dst = ServingEngine(params, cfg, s_cfg, c_cfg, replica_id=rid)
        dst.clock.tick("own", rid)
        if share:
            dst.clock.clock = bc.merge(dst.clock.clock, engine.clock.clock)
        want = bool(bc.ordering(session["clock"].clock,
                                dst.clock.clock).a_le_b)
        got = bool(dst.adopt_many([session])[0])
        assert got == want == share, (rid, got, want)
        verdicts[rid] = "accept" if got else "refuse"
    return {"arch": cfg.name, "layers": cfg.n_layers, "d_model": cfg.d_model,
            "vocab": cfg.vocab, "batch": batch, "prompt": prompt_len,
            "new_tokens": gen, "first_run_s": runs[0][1],
            "second_run_s": runs[1][1], "migrations": verdicts}


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def _sharded_fleet(n, m, seed):
    """Random peer clocks with per-row offsets (non-uniform §4 bases),
    a few promoted (> u8 span) rows, and chain-related rows."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import clock as bc
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 20, (n, m)) + rng.integers(0, 300, (n, 1))
    rows[1::3] = rows[0::3][: len(rows[1::3])] + \
        (rng.random((len(rows[1::3]), m)) < 0.05)   # descendants
    rows[5, 7] += 700                              # promoted rows
    rows[9, 3] += 900
    return {f"peer{i}": bc.BloomClock(jnp.asarray(rows[i], jnp.int32),
                                      jnp.zeros((), jnp.int32), 4)
            for i in range(n)}


def _ring_dispatches(ops) -> int:
    return sum(n for (op, engine, _), n in ops.DISPATCHES.items()
               if op == "matrix" and engine == "ring_full")


def phase_sharded(shards: int = 4) -> dict:
    import jax
    import numpy as np
    from repro.causal import CausalPolicy
    from repro.core import clock as bc
    from repro.fleet import ClockRegistry, GossipConfig, gossip_round
    from repro.fleet.transport import (MeshCollectiveTransport,
                                       anti_entropy_session)
    from repro.kernels import ops
    from repro.launch.mesh import make_fleet_mesh

    n, m = 4096, 1024
    assert len(jax.devices()) >= shards, jax.devices()
    mesh = make_fleet_mesh(shards)
    peers = _sharded_fleet(n, m, 3)
    local = bc.merge(peers["peer0"], peers["peer3"])
    gone = [f"peer{i}" for i in range(2, n, 97)]     # dead slots

    def filled(mesh_):
        reg = ClockRegistry(capacity=n, m=m, k=4, mesh=mesh_)
        reg.admit_many(peers)
        reg.evict_many(gone)
        return reg

    one, sharded = filled(None), filled(mesh)
    spread = len(sharded.cells_u8.sharding.device_set)
    assert spread == shards, f"slab spans {spread} devices, not {shards}"
    out = {"rows": n, "m": m, "shards": shards, "dead": len(gone)}

    ref, _ = _timed(lambda: one.classify_all(local))
    got, t = _timed(lambda: sharded.classify_all(local))
    _same("classify status", got.status, ref.status)
    _same("classify fp", got.fp, ref.fp)
    _same("classify sums", got.sums, ref.sums)
    out["classify_first_call_s"] = t
    out["classify_counts"] = got.counts()

    ref_p = jax.device_get(one.all_pairs())
    rings = _ring_dispatches(ops)
    res_p, t = _timed(lambda: sharded.all_pairs())
    assert _ring_dispatches(ops) > rings, "all_pairs did not run the ring"
    spread = len(res_p["a_le_b"].sharding.device_set)
    assert spread == shards, f"pairs span {spread} devices"
    got_p = jax.device_get(res_p)
    for key in ("a_le_b", "b_le_a", "concurrent", "fp", "row_sums",
                "col_sums"):
        _same(f"all_pairs {key}", got_p[key], ref_p[key])
    out["all_pairs_engine"] = res_p.engine
    out["all_pairs_first_call_s"] = t

    gcfg = GossipConfig(policy=CausalPolicy(fp_threshold=1.0))
    g_one, g_sh = filled(None), filled(mesh)
    m_ref, r_ref = gossip_round(g_one, local, gcfg)
    (m_got, r_got), t = _timed(lambda: anti_entropy_session(
        g_sh, local, MeshCollectiveTransport(g_sh), gcfg))
    assert r_got.transport == "mesh" and r_got.shards == shards
    for mask in ("accepted", "quarantined", "stragglers", "unconfident"):
        _same(f"gossip {mask}", getattr(r_got, mask), getattr(r_ref, mask))
    _same("gossip fp", r_got.view.fp, r_ref.view.fp)
    _same("gossip merged", m_got.logical_cells(), m_ref.logical_cells())
    assert r_got.pushback_bytes == r_ref.pushback_bytes
    out["gossip_s"] = t
    out["gossip_accepted"] = int(np.asarray(r_got.accepted).sum())
    out["gossip_digest_bytes"] = r_got.digest_bytes
    return out


ONE_CHIP = ("ovm", "pairs", "hybrid", "runtime", "serve", "model")
PHASES = {"ovm": phase_ovm, "pairs": phase_pairs, "hybrid": phase_hybrid,
          "runtime": phase_runtime, "serve": phase_serve,
          "model": phase_model, "sharded": phase_sharded}


def run_phases(names) -> bool:
    """Run each phase; print one line per phase; True when all passed."""
    ok = True
    for name in names:
        c0, t0 = _COMPILE_S[0], time.perf_counter()
        try:
            info = PHASES[name]()
            status = "PASS"
        except Exception as e:      # report the phase, go on to the next
            traceback.print_exc()
            info, status, ok = {"error": f"{type(e).__name__}: {e}"}, \
                "FAIL", False
        info["wall_s"] = time.perf_counter() - t0
        info["compile_s"] = _COMPILE_S[0] - c0
        print(f"[{name}] {status} " + json.dumps(info, default=str),
              flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip path")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    _listen_compiles()
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)

    names = ("sharded",) if args.chips == 4 else ONE_CHIP
    ok = run_phases(names)
    interpreted = sorted({op for op, _, interp in ops.DISPATCHES if interp})
    compiled = sum(n for (_, _, interp), n in ops.DISPATCHES.items()
                   if not interp)
    print(f"[dispatch] compiled={compiled} interpreted={interpreted}",
          flush=True)
    if interpreted or not compiled:
        print("chip_smoke: kernels ran in the Pallas interpreter "
              f"({interpreted}) or not at all", file=sys.stderr)
        ok = False
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
