"""The bloom-clock partial order and Eq. 3, written out plainly.

Copied in substance from the bring-up smoke run's references: cell-wise
dominance over logical cells (the paper's §3), both clocks' sums, and
Eq. 3 ``(1 - (1 - 1/m)^ΣB)^ΣA`` evaluated on the host CPU in the
log-stable form, so the chip's fp is held to the CPU's exp/log and not
to its own.  ``dtype`` lowers the precision of the sums and of Eq. 3:
``"bfloat16"`` is the control that every limit must reject.

Verdict names follow the service's convention for a peer p (a session
or a registry row) against the local clock q: ``ancestor`` p ≼ q,
``descendant`` q ≼ p, ``same`` both, ``forked`` neither.
"""
from __future__ import annotations

import functools

import numpy as np

VERDICTS = ("ancestor", "same", "descendant", "forked")
CODE = {name: i for i, name in enumerate(VERDICTS)}
TINY = float(np.finfo(np.float32).tiny)


def order_host(p: np.ndarray, q: np.ndarray):
    """(p ≼ q, q ≼ p, Σp, Σq) of logical rows ``p`` [n, m] against ``q``
    ([m] or [n, m]), on the host (sums in int64)."""
    p = np.asarray(p)
    q = np.broadcast_to(np.asarray(q), p.shape)
    return ((p <= q).all(axis=1), (q <= p).all(axis=1),
            p.sum(axis=1, dtype=np.int64), q.sum(axis=1, dtype=np.int64))


@functools.lru_cache(maxsize=None)
def _order_dev_fn(dtype: str):
    import jax
    import jax.numpy as jnp

    def order(rows, q):
        if dtype != "float32":
            # the control: cells and sums in the lower precision
            rows, q = rows.astype(dtype), q.astype(dtype)
            sums = jnp.sum(rows, axis=1, dtype=dtype).astype(jnp.float32)
        else:
            sums = jnp.sum(rows, axis=1).astype(jnp.float32)
        return (jnp.all(rows <= q[None, :], axis=1),
                jnp.all(q[None, :] <= rows, axis=1), sums)

    return jax.jit(order)


def order_device(rows, q, dtype: str = "float32"):
    """(p ≼ q, q ≼ p, Σp) of a block of logical rows on the device."""
    import jax
    return jax.device_get(_order_dev_fn(dtype)(rows, q))


@functools.lru_cache(maxsize=None)
def _eq3_fn(m: int, dtype: str):
    import jax
    import jax.numpy as jnp

    def eq3(sum_a, sum_b):
        a = sum_a.astype(dtype)
        b = sum_b.astype(dtype)
        log_q = jnp.log1p(jnp.asarray(-1.0 / m, dtype))
        inner = jnp.clip(-jnp.expm1(b * log_q), jnp.asarray(1e-30, dtype),
                         jnp.asarray(1.0, dtype))
        return jnp.exp(a * jnp.log(inner)).astype(jnp.float32)

    return jax.jit(eq3)


def eq3_cpu(sum_a, sum_b, m: int, dtype: str = "float32") -> np.ndarray:
    """Eq. 3 fp of "A before B", evaluated on the host CPU."""
    import jax
    cpu = jax.devices("cpu")[0]
    a = jax.device_put(np.asarray(sum_a, np.float32), cpu)
    b = jax.device_put(np.asarray(sum_b, np.float32), cpu)
    return np.asarray(_eq3_fn(m, dtype)(a, b))


def verdicts(p_le_q: np.ndarray, q_le_p: np.ndarray) -> np.ndarray:
    """Verdict codes (indices into ``VERDICTS``)."""
    p_le_q, q_le_p = np.asarray(p_le_q, bool), np.asarray(q_le_p, bool)
    out = np.full(p_le_q.shape, CODE["forked"], np.int8)
    out[p_le_q] = CODE["ancestor"]
    out[q_le_p] = CODE["descendant"]
    out[p_le_q & q_le_p] = CODE["same"]
    return out


def claimed_fp(code: np.ndarray, sum_p, sum_q, m: int,
               dtype: str = "float32") -> np.ndarray:
    """Eq. 3 fp of the direction each verdict claims; ``same`` and
    ``forked`` are exact and carry 0."""
    sum_p = np.asarray(sum_p, np.float32)
    sum_q = np.broadcast_to(np.asarray(sum_q, np.float32), sum_p.shape)
    fp = np.zeros(sum_p.shape, np.float32)
    anc = code == CODE["ancestor"]
    dsc = code == CODE["descendant"]
    if anc.any():
        fp[anc] = eq3_cpu(sum_p[anc], sum_q[anc], m, dtype)
    if dsc.any():
        fp[dsc] = eq3_cpu(sum_q[dsc], sum_p[dsc], m, dtype)
    return fp


def fp_rel_err(got, want) -> np.ndarray:
    """Relative error of fp per element.  The chip flushes float32
    subnormals to zero, so a gap within the smallest normal counts as
    none, and a want below it is measured against it."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.maximum(np.abs(got - want) - TINY, 0.0)
    rel = err / np.maximum(np.abs(want), TINY)
    return np.where(np.isfinite(got), rel, np.inf)
