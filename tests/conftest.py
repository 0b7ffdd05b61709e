import os

# The sharded-fleet harness (tests/test_sharded_fleet.py) shard_maps the
# registry kernels over a mesh, which needs multiple devices — and on
# the CPU host platform they must be forced BEFORE jax initializes its
# backend, so this happens at conftest import, not in a fixture body.
# 8 forced host devices are harmless for the single-device tests
# (unsharded work runs on device 0); the dry-run sets its own XLA_FLAGS
# in its own process and never inherits these.
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax
import numpy as np
import pytest

# smoke tests / benches must see the CPU platform regardless of build.
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def host_devices():
    """The forced 8-device host platform the shard_map tests run on."""
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip(
            f"needs 8 forced host devices, have {len(devs)} "
            "(jax initialized before conftest set XLA_FLAGS?)")
    return devs


def _mask_fold(res, alive: np.ndarray, capacity: int,
               local_sum: float | None = None):
    """The boolean-mask fold ``view_from_classify`` ran on the host
    before the fold moved to one elementwise select chain, verbatim: the
    oracle the select chain is pinned against, bit for bit.  ``res`` is
    a host (``device_get``) ``ClassifyResult``."""
    from repro.fleet.registry import (ANCESTOR, DEAD, DESCENDANT, FORKED,
                                      SAME, FleetView)
    alive = np.asarray(alive, bool)
    p_le_q = res.after()           # peer ≼ local
    q_le_p = res.before()          # local ≼ peer
    equal = res.equal()
    status = np.full(capacity, FORKED, np.int8)
    status[p_le_q] = ANCESTOR
    status[q_le_p] = DESCENDANT
    status[equal] = SAME
    status[~alive] = DEAD
    # fp of the direction actually claimed; SAME and FORKED are exact
    fp = np.asarray(res.claimed_fp(), np.float32)
    fp[~alive] = 0.0
    return FleetView(
        status=status,
        fp=fp,
        sums=res.sum_p,
        alive=alive.copy(),
        local_sum=float(res.sum_q) if local_sum is None else local_sum,
        engine=res.engine or "",
    )


@pytest.fixture(scope="session")
def mask_fold():
    """``mask_fold(host_result, alive, capacity) -> FleetView``."""
    return _mask_fold


def _assert_views_bit_identical(got, want):
    """Every ``FleetView`` field equal bit for bit, dtypes and host
    numpy arrays included."""
    for name, dtype in (("status", np.int8), ("fp", np.float32),
                        ("sums", np.float32), ("alive", np.bool_)):
        g, w = getattr(got, name), np.asarray(getattr(want, name))
        assert type(g) is np.ndarray and g.dtype == dtype, name
        assert g.shape == w.shape, name
        np.testing.assert_array_equal(
            g.view(np.uint8), w.view(np.uint8), err_msg=name)
    assert got.local_sum == want.local_sum
    assert got.engine == want.engine


@pytest.fixture(scope="session")
def views_bit_identical():
    return _assert_views_bit_identical
