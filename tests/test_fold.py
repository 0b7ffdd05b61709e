"""The classify fold: flags to status codes + claimed-direction fp.

``view_from_classify`` folds a device ``ClassifyResult`` on the device
and a host one in numpy, with one elementwise select chain.  Every case
here pins the resulting ``FleetView`` bit for bit against the
boolean-mask fold it replaced (the ``mask_fold`` oracle in
``conftest.py``), over all five statuses, dead slots, the int32 rim and
the tiered registry's warm and cold folds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.causal import CausalPolicy, ClassifyResult
from repro.core import clock as bc
from repro.fleet import (ANCESTOR, DEAD, DESCENDANT, FORKED, SAME,
                         ClockRegistry, view_from_classify)
from repro.obs import MetricsRecorder, Observer
from repro.serve import tiers as tiers_mod
from repro.serve.tiers import TierConfig, TieredRegistry

M, K = 64, 3
ALL_STATUSES = {DEAD, ANCESTOR, SAME, DESCENDANT, FORKED}


def _clock(row) -> bc.BloomClock:
    return bc.BloomClock(jnp.asarray(row, jnp.int32),
                         jnp.zeros((), jnp.int32), K)


def _random_result(seed: int, n: int, on_device: bool):
    """Random flags and fp spanning thirty decades, with exact 0 and 1."""
    rng = np.random.default_rng(seed)
    fps = [(10.0 ** rng.uniform(-30, 0, n)).astype(np.float32)
           for _ in range(2)]
    for fp in fps:
        fp[rng.random(n) < 0.05] = 0.0
        fp[rng.random(n) < 0.05] = 1.0
    leaves = dict(
        q_le_p=rng.random(n) < 0.5, p_le_q=rng.random(n) < 0.5,
        sum_q=np.float32(rng.uniform(0, 1e6)),
        sum_p=rng.uniform(0, 1e6, n).astype(np.float32),
        fp_q_before_p=fps[0], fp_p_before_q=fps[1])
    host = ClassifyResult.from_dict(leaves, engine="packed")
    res = jax.device_put(host) if on_device else host
    return res, host, rng.random(n) < 0.8


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("where", ["device", "host"])
def test_fold_matches_mask_fold_on_random_flags(where, seed, mask_fold,
                                                views_bit_identical):
    n = 4096
    res, host, alive = _random_result(seed, n, on_device=where == "device")
    assert isinstance(res.q_le_p, jax.Array) == (where == "device")
    obs = Observer(metrics=MetricsRecorder())
    got = view_from_classify(res, alive, n, obs=obs)
    want = mask_fold(host, alive, n)
    assert set(np.unique(want.status)) == ALL_STATUSES
    views_bit_identical(got, want)
    folds = {w: obs.metrics.counter("registry_fold", where=w).value
             for w in ("device", "host")}
    assert folds == {"device": int(where == "device"),
                     "host": int(where == "host")}
    readback = obs.metrics.counter("registry_readback_bytes").value
    assert readback == (9 * n + 4 if where == "device" else 0)


def _lineage_fleet(n: int, seed: int, wide: bool):
    """Ancestors, equals, descendants and forks of one local clock, in
    turn; with ``wide`` one descendant spans past a byte (int32 rim)."""
    rng = np.random.default_rng(seed)
    local = rng.integers(8, 24, M)
    rows = []
    for i in range(n):
        step = rng.integers(0, 4, M)
        kind = i % 4
        if kind == 0:
            rows.append(local - step)
        elif kind == 1:
            rows.append(local)
        elif kind == 2:
            rows.append(local + step)
        else:
            rows.append(local + np.where(np.arange(M) % 2, step, -step) + 1)
    if wide:
        rows[2] = local + np.arange(M) * 8
    return {f"peer{i}": _clock(r) for i, r in enumerate(rows)}, _clock(local)


@pytest.mark.parametrize("wide", [False, True], ids=["packed", "rim"])
@pytest.mark.parametrize("dead", [False, True], ids=["full", "dead"])
def test_classify_all_matches_mask_fold(dead, wide, mask_fold,
                                        views_bit_identical):
    capacity = 64
    peers, local = _lineage_fleet(capacity, seed=11, wide=wide)
    obs = Observer(metrics=MetricsRecorder())
    reg = ClockRegistry(capacity=capacity, m=M, k=K,
                        policy=CausalPolicy(observer=obs))
    reg.admit_many(peers)
    if dead:
        reg.evict_many([f"peer{i}" for i in range(0, capacity, 5)])
    assert reg.packed != wide
    got = reg.classify_all(local)
    want = mask_fold(jax.device_get(reg.engine.classify(local, reg._slab())),
                     reg._alive_host, capacity)
    assert ("wide_overlay" in got.engine) == wide
    assert set(np.unique(want.status)) == (
        ALL_STATUSES if dead else ALL_STATUSES - {DEAD})
    views_bit_identical(got, want)
    assert obs.metrics.counter("registry_fold", where="device").value == 1


@pytest.mark.parametrize("tier", ["warm", "cold"])
def test_tier_folds_match_mask_fold(tier, monkeypatch, mask_fold,
                                    views_bit_identical):
    """The tiered registry's warm and cold folds hand their device
    results to the device fold; each view equals the mask fold of the
    same result read back."""
    cfg = TierConfig(hot_capacity=6, warm_capacity=10, promote_after=2,
                     demote_batch=2, spill_batch=4, cold_batch=4)
    folded = {"warm": 0, "cold": 0}

    def checked(res, alive, capacity, local_sum=None, **kw):
        got = view_from_classify(res, alive, capacity, local_sum, **kw)
        assert isinstance(res.q_le_p, jax.Array)
        views_bit_identical(
            got, mask_fold(jax.device_get(res), alive, capacity))
        folded["warm" if capacity == cfg.warm_capacity else "cold"] += 1
        return got

    monkeypatch.setattr(tiers_mod, "view_from_classify", checked)
    peers, local = _lineage_fleet(30, seed=4, wide=True)
    t = TieredRegistry(cfg, m=M, k=K)
    t.admit_many(peers)
    assert set(t._tier_of.values()) == {"hot", "warm", "cold"}
    view = t.classify(local)
    t.close()
    assert folded[tier] >= 1
    assert tier in view.tier
