"""Integration tests: the paper's technique load-bearing in the framework.

- checkpoint lineage gating (restore from ancestor OK, fork refused)
- async local-SGD with clock-guarded merges (forked pod quarantined,
  straggler skipped, training still converges)
- serving session migration gated by clock comparison
- elastic reshard restore
- end-to-end train loss decreases
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_smoke_config
from repro.core import clock as bc
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.params import init_params
from repro.optim.adamw import OptConfig
from repro.runtime.async_trainer import (AsyncConfig, AsyncCoordinator,
                                         run_pod_round)
from repro.runtime.clock_runtime import ClockConfig, ClockRuntime, LineageStatus
from repro.runtime.training import (cross_entropy, init_train_state,
                                    make_train_step)
from repro.serving.engine import ServeConfig, ServingEngine

KEY = jax.random.PRNGKey(0)
CFG = get_smoke_config("qwen1_5_0_5b")


def _mk_batch(data, step):
    b = data.batch(step)
    hi, lo = data.event_id(step)
    b["ev_hi"] = jnp.uint32(hi)
    b["ev_lo"] = jnp.uint32(lo)
    return b


class TestTrainLoop:
    def test_loss_decreases(self):
        opt = OptConfig(lr=3e-3, total_steps=40)
        ck = ClockConfig(m=128)
        state = init_train_state(KEY, CFG, opt, ck)
        step_fn = jax.jit(make_train_step(CFG, opt, ck))
        data = SyntheticLM(DataConfig(vocab=CFG.vocab, seq_len=64, global_batch=8))
        losses = []
        for s in range(40):
            state, m = step_fn(state, _mk_batch(data, s))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.5
        # clock ticked once per step
        assert float(jnp.sum(state.clock_cells)) == 40 * ck.k

    def test_microbatched_grads_match(self):
        opt = OptConfig(lr=1e-3, total_steps=10)
        ck = ClockConfig(m=64)
        cfg32 = dataclasses.replace(CFG, dtype="float32")
        state = init_train_state(KEY, cfg32, opt, ck)
        data = SyntheticLM(DataConfig(vocab=CFG.vocab, seq_len=32, global_batch=8))
        b = _mk_batch(data, 0)
        s1, m1 = jax.jit(make_train_step(cfg32, opt, ck, num_microbatches=1))(state, b)
        s4, m4 = jax.jit(make_train_step(cfg32, opt, ck, num_microbatches=4))(state, b)
        np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                                   rtol=1e-4)
        for k in list(state.params)[:4]:
            np.testing.assert_allclose(np.asarray(s1.params[k]),
                                       np.asarray(s4.params[k]),
                                       rtol=2e-4, atol=2e-5)


class TestCheckpointLineage:
    def test_save_restore_roundtrip(self, tmp_path):
        opt = OptConfig(total_steps=10)
        ck = ClockConfig(m=64)
        state = init_train_state(KEY, CFG, opt, ck)
        rt = ClockRuntime(ck, run_id="t0")
        rt.tick_step(0)
        mgr = CheckpointManager(str(tmp_path), run_id="t0")
        mgr.save(1, state, rt.snapshot(), block=True)
        restored, manifest = mgr.restore(target_structure=state)
        assert manifest["step"] == 1
        for k in list(state.params)[:3]:
            np.testing.assert_array_equal(np.asarray(state.params[k]),
                                          np.asarray(restored.params[k]))

    def test_ancestor_restore_admitted_fork_refused(self, tmp_path):
        ck = ClockConfig(m=256, fp_threshold=0.5)
        live = ClockRuntime(ck, run_id="r")
        ckpt = ClockRuntime(ck, run_id="r")
        # shared prefix
        for s in range(5):
            live.tick_step(s)
            ckpt.tick_step(s)
        # live advances beyond the checkpoint -> checkpoint is an ancestor
        live.tick_step(5)
        ok, status, fp = live.admit_restore(ckpt.clock)
        assert status == LineageStatus.ANCESTOR and ok
        # forked checkpoint: ticked an event live never saw
        forked = ClockRuntime(ck, run_id="r")
        for s in range(5):
            forked.tick_step(s)
        forked.tick("rogue-event")
        live.tick_step(6)
        ok2, status2, _ = live.admit_restore(forked.clock)
        assert status2 == LineageStatus.FORKED and not ok2

    def test_elastic_reshard_restore(self, tmp_path):
        """Restore under a different mesh: leaves land with new shardings."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        opt = OptConfig(total_steps=10)
        ck = ClockConfig(m=64)
        state = init_train_state(KEY, CFG, opt, ck)
        mgr = CheckpointManager(str(tmp_path), run_id="t0")
        rt = ClockRuntime(ck)
        mgr.save(1, state, rt.snapshot(), block=True)
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        shardings = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), state)
        restored, _ = mgr.restore(target_structure=state, shardings=shardings)
        leaf = restored.params["layers/attn/wq"]
        assert leaf.sharding.mesh.shape == {"data": 1}


class TestAsyncClockGuard:
    def _setup(self):
        cfg32 = dataclasses.replace(CFG, dtype="float32")
        opt = OptConfig(lr=2e-3, total_steps=200)
        params = init_params(KEY, cfg32)
        a_cfg = AsyncConfig(n_pods=3, local_steps=3, outer_lr=0.5)
        c_cfg = ClockConfig(m=256, fp_threshold=1.0 - 1e-6, straggler_gap=1e9)
        coord = AsyncCoordinator(params, a_cfg, c_cfg)
        pods = coord.add_pods(list(range(a_cfg.n_pods)), c_cfg)
        data = SyntheticLM(DataConfig(vocab=cfg32.vocab, seq_len=32,
                                      global_batch=4))

        def loss_fn(p, batch):
            from repro.models import transformer as T
            logits, _ = T.forward_train(p, cfg32, batch["tokens"])
            return cross_entropy(logits, batch["labels"], cfg32.vocab)

        @jax.jit
        def sgd_step(p, batch):
            l, g = jax.value_and_grad(loss_fn)(p, batch)
            return jax.tree.map(lambda w, gr: w - 2e-3 * gr, p, g), l

        def data_fn(pod_id, step):
            return data.batch(step * 10 + pod_id)

        return coord, pods, a_cfg, sgd_step, data_fn

    def test_healthy_pods_all_merge(self):
        coord, pods, a_cfg, sgd_step, data_fn = self._setup()
        deltas = {}
        for pod in pods:
            d, _ = run_pod_round(pod, sgd_step, data_fn, a_cfg, 0)
            deltas[pod.pod_id] = d
        decisions = coord.outer_step(pods, deltas)
        assert all(ok for ok, _, _ in decisions.values())

    def test_elastic_pod_churn_never_exhausts_registry(self):
        """Retired pod ids free their registry slots: churning through
        many more distinct pods than the slab holds must keep working."""
        coord, pods, a_cfg, sgd_step, data_fn = self._setup()
        cap = coord.registry.capacity
        c_cfg = coord.clock.cfg
        next_id = len(pods)
        for rnd in range(3):
            deltas = {}
            for pod in pods:
                d, _ = run_pod_round(pod, sgd_step, data_fn, a_cfg, rnd)
                deltas[pod.pod_id] = d
            decisions = coord.outer_step(pods, deltas)
            assert all(ok for ok, _, _ in decisions.values()), decisions
            # full fleet replacement each round: cap+ distinct ids total
            pods = coord.add_pods(
                list(range(next_id, next_id + cap // 2)), c_cfg)
            next_id += cap // 2
        assert len(coord.registry) <= cap

    def test_forked_pod_quarantined(self):
        """A pod restored from a pre-commit snapshot that then does local
        work is CONCURRENT with the advanced coordinator -> quarantined.
        (The fork is only detectable once the coordinator has committed a
        round the pod missed — correct causality semantics.)"""
        coord, pods, a_cfg, sgd_step, data_fn = self._setup()
        deltas = {}
        stale_snapshot = None
        for pod in pods:
            d, _ = run_pod_round(pod, sgd_step, data_fn, a_cfg, 0)
            deltas[pod.pod_id] = d
            if pod.pod_id == 2:
                stale_snapshot = pod.clock.clock  # pre-commit state
        decisions = coord.outer_step(pods, deltas)  # commit round 0
        assert all(ok for ok, _, _ in decisions.values())
        # pod 2 crashes, restores the stale snapshot, works independently
        pods[2].clock.clock = stale_snapshot
        deltas2 = {}
        for pod in pods:
            d, _ = run_pod_round(pod, sgd_step, data_fn, a_cfg, 50)
            deltas2[pod.pod_id] = d
        decisions2 = coord.outer_step(pods, deltas2)
        assert decisions2[0][0] and decisions2[1][0]
        assert not decisions2[2][0]
        assert decisions2[2][1] == LineageStatus.FORKED

    def test_straggler_skipped_then_catches_up(self):
        coord, pods, a_cfg, sgd_step, data_fn = self._setup()
        # tighten straggler gap: one idle round (12 missed ticks) trips it
        coord_cfg = dataclasses.replace(coord.clock.cfg, straggler_gap=4.0)
        coord.clock.cfg = coord_cfg
        deltas = {}
        for pod in pods[:2]:  # pod 2 does no work this round
            d, _ = run_pod_round(pod, sgd_step, data_fn, a_cfg, 0)
            deltas[pod.pod_id] = d
        deltas[2] = jax.tree.map(jnp.zeros_like, deltas[0])
        decisions = coord.outer_step(pods, deltas)
        assert not decisions[2][0] and decisions[2][1] == "straggler"
        # pod 2 resyncs to the published UNION clock -> its sum equals the
        # fleet's; after one working round it is re-admitted
        pods[2].clock.clock = bc.merge(pods[2].clock.clock, coord.clock.clock)
        d, _ = run_pod_round(pods[2], sgd_step, data_fn, a_cfg, 100)
        for pod in pods[:2]:
            deltas[pod.pod_id], _ = run_pod_round(pod, sgd_step, data_fn,
                                                  a_cfg, 100)
        deltas[2] = d
        decisions2 = coord.outer_step(pods, deltas)
        assert decisions2[2][0], decisions2


class TestServing:
    def test_generate_and_migration_guard(self):
        cfg32 = dataclasses.replace(CFG, dtype="float32")
        params = init_params(KEY, cfg32)
        c_cfg = ClockConfig(m=256, fp_threshold=1.0 - 1e-6)
        eng_a = ServingEngine(params, cfg32, ServeConfig(max_seq=64), c_cfg,
                              replica_id="A")
        prompts = jax.random.randint(KEY, (2, 8), 0, cfg32.vocab)
        sess = eng_a.admit(prompts)
        toks = eng_a.generate(sess, 4)
        assert toks.shape == (2, 4)
        # greedy decode must match teacher-forced continuation argmax
        # replica B that shares A's history can adopt the session
        eng_b = ServingEngine(params, cfg32, ServeConfig(max_seq=64), c_cfg,
                              replica_id="B")
        eng_b.clock.clock = bc.merge(eng_b.clock.clock, eng_a.clock.clock)
        ok, status, _ = eng_b.can_adopt(sess)
        assert ok, status
        # a fresh replica that never saw the session's history must refuse
        eng_c = ServingEngine(params, cfg32, ServeConfig(max_seq=64), c_cfg,
                              replica_id="C")
        eng_c.clock.tick("own-history")
        ok2, status2, _ = eng_c.can_adopt(sess)
        assert not ok2 and status2 == LineageStatus.FORKED
        # bulk migration agrees with the scalar guard in one kernel call
        mask = eng_b.adopt_many([sess])
        assert list(mask) == [True]
        assert sess["sid"] in eng_b.sessions

    def test_session_registry_bounded_and_releasable(self):
        """The session-clock registry must never crash a long-running
        engine: oldest sessions evict FIFO at capacity, release() frees
        slots, adopt() writes the minted sid back."""
        cfg32 = dataclasses.replace(CFG, dtype="float32")
        params = init_params(KEY, cfg32)
        c_cfg = ClockConfig(m=128, fp_threshold=1.0 - 1e-6)
        eng = ServingEngine(params, cfg32, ServeConfig(max_seq=64), c_cfg,
                            replica_id="A")
        cap = eng.sessions.capacity
        prompts = jax.random.randint(KEY, (2, 8), 0, cfg32.vocab)
        last = None
        for _ in range(cap + 3):
            last = eng.admit(prompts)
        assert len(eng.sessions) == cap          # FIFO-bounded, no raise
        assert last["sid"] in eng.sessions       # newest survives
        eng.release(last)
        assert last["sid"] not in eng.sessions
        assert len(eng.sessions) == cap - 1
        migrated = {"clock": last["clock"]}
        assert eng.adopt(migrated)
        assert migrated["sid"] in eng.sessions   # sid written back


class TestSimulatorVsPaper:
    def test_fig6_style_trace(self):
        """5-node hand trace mirroring paper Fig. 6 semantics."""
        m, k = 8, 2
        clocks = {n: bc.zeros(m, k) for n in "ABCDE"}

        def ev(node, i):
            clocks[node] = bc.tick(clocks[node], jnp.uint32(0), jnp.uint32(i))
            return clocks[node]

        def recv(dst, snapshot):
            clocks[dst] = bc.merge(clocks[dst], snapshot)

        t1 = ev("A", 1)
        for n in "BDE":       # C missed A's broadcast
            recv(n, t1)
        t2 = ev("B", 2)
        for n in "AE":        # C, D missed
            recv(n, t2)
        # A,B,E identical so far; D only saw t1; C nothing
        assert bool(bc.ordering(clocks["A"], clocks["E"]).equal)
        assert bool(bc.ordering(clocks["D"], clocks["A"]).a_le_b)
        t3 = ev("D", 3)       # D advances independently of t2
        o = bc.ordering(clocks["D"], clocks["E"])
        # D(t1+t3) vs E(t1+t2): concurrent — exactly the paper's first
        # incomparable pair
        assert bool(o.concurrent)
        recv("E", t3)         # E merges -> dominates everyone now
        for n in "ABCD":
            assert bool(bc.ordering(clocks[n], clocks["E"]).a_le_b)

    def test_eq3_against_monte_carlo_band(self):
        """Eq. 3 is a (conservative) approximation: MC-true overlap must not
        EXCEED the Eq. 3 prediction for these regimes."""
        from repro.core.sim import monte_carlo_overlap

        for m, sa, sb in [(6, 7, 10), (64, 20, 60), (128, 50, 100)]:
            pred = float(bc.fp_rate(sa, sb, m))
            mc = monte_carlo_overlap(m, sa, sb, trials=30_000, seed=1)
            assert mc <= pred + 0.02, (m, sa, sb, mc, pred)
