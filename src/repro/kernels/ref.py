"""Pure-jnp oracles for the bloom-clock kernels (ground truth for tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["bloom_tick_ref", "bloom_merge_compare_ref", "eq3_fp"]

_EQ3_CLIP = 1e-30


def eq3_fp(x_sum, y_sum, m: int):
    """Eq. 3: the chance that a clock with ``x_sum`` total increments
    sits <= one with ``y_sum`` by coincidence, over m cells.

    ``exp(x * log(1 - (1 - 1/m)^y))`` in log1p/expm1-stable form.  This
    is THE expression: every kernel wrapper finalizes its sums through
    it, so kernel fp is the reference fp by construction."""
    log_q = jnp.log1p(-1.0 / m)
    inner = jnp.clip(-jnp.expm1(y_sum * log_q), _EQ3_CLIP, 1.0)
    return jnp.exp(x_sum * jnp.log(inner))


def bloom_tick_ref(cells: jax.Array, probes: jax.Array) -> jax.Array:
    """cells [B, m] int32, probes [B, P] int32 -> incremented cells.

    Straightforward one-hot formulation (what the kernel must match).
    """
    m = cells.shape[-1]
    one_hot = jax.nn.one_hot(probes, m, dtype=cells.dtype)  # [B, P, m]
    return cells + jnp.sum(one_hot, axis=-2)


def bloom_merge_compare_ref(a: jax.Array, b: jax.Array):
    """Returns (merged, flags[B,2] int32, sums[B,2] f32, fp[B,2] f32).

    flags[:, 0] = all(a<=b), flags[:, 1] = all(a>=b)
    sums[:, 0] = ΣA, sums[:, 1] = ΣB
    fp[:, 0]   = Eq.3 fp of "A -> B", fp[:, 1] = "B -> A"
    """
    m = a.shape[-1]
    merged = jnp.maximum(a, b)
    le = jnp.all(a <= b, axis=-1)
    ge = jnp.all(a >= b, axis=-1)
    sa = jnp.sum(a, axis=-1).astype(jnp.float32)
    sb = jnp.sum(b, axis=-1).astype(jnp.float32)
    fp_ab = eq3_fp(sa, sb, m)
    fp_ba = eq3_fp(sb, sa, m)
    flags = jnp.stack([le, ge], axis=-1).astype(jnp.int32)
    sums = jnp.stack([sa, sb], axis=-1)
    fp = jnp.stack([fp_ab, fp_ba], axis=-1)
    return merged, flags, sums, fp
