"""The Bloom Clock (Ramabaja, 2019) as a composable JAX module.

A clock is a counting bloom filter of ``m`` int32 cells plus a scalar
``base`` implementing the paper's §4 compression: the logical cell value is
``base + cells[i]``.  All operations are pure functions over pytrees and are
jit/vmap/pjit compatible; batched clocks simply carry leading batch dims.

Paper-op mapping:
  tick        §3 step 2  (hash event k times, increment cells)
  merge       §3 step 3  (element-wise max)
  ordering    §3          (cell-wise dominance; exact concurrency detection)
  fp_rate     §3 Eq. 3    ((1-(1-1/m)^{ΣB})^{ΣA}), log-stable
  compress    §4          ((c)[residuals] base-offset form)

**Bounded-counter semantics** (practically-self-stabilizing vector
clocks): int32 counters live on the mod-2^32 circle, so every compare /
max / min below is derived from the *wrap-subtraction* ``a - b`` — in
two's complement that difference is the correct signed distance
whenever the true gap is under 2^31, even when one side has wrapped
past ``INT32_MAX`` and the other has not.  For clocks in the sane range
(everything far from the wrap point) the derived predicates are
bit-identical to the direct ``<=`` / ``maximum`` forms, which is what
keeps every kernel bit-identity pin intact; near the wrap point they
keep returning the right answer where the direct forms silently invert.
The same derivation runs inside the Pallas kernels
(``repro.kernels.template``).

The hot paths (tick / fused merge+compare) have Pallas TPU kernels in
``repro.kernels``; this module is the reference implementation.  For
comparisons, the public surface is ``repro.causal`` (``causal.compare``
for typed pairwise results, ``CausalEngine`` for the bulk verbs);
``ordering`` is the in-module reference the internal helpers
(``happened_before``, ``comparability_matrix``) build on.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hashing import bloom_indices

__all__ = [
    "BloomClock",
    "zeros",
    "tick",
    "merge",
    "ordering",
    "Ordering",
    "fp_rate",
    "compress",
    "decompress",
    "clock_sum",
    "residual_span",
    "to_wire",
    "from_wire",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BloomClock:
    """Counting-bloom-filter logical clock.

    cells: int32[..., m] residual counters.
    base:  int32[...]    shared offset (paper §4 compression); logical
                         value of cell i is base + cells[i].
    k:     static number of hash probes per event.
    """

    cells: jax.Array
    base: jax.Array
    k: int = 4

    # -- pytree protocol (k is static) --
    def tree_flatten(self):
        return (self.cells, self.base), self.k

    @classmethod
    def tree_unflatten(cls, k, leaves):
        return cls(leaves[0], leaves[1], k)

    @property
    def m(self) -> int:
        return self.cells.shape[-1]

    @property
    def batch_shape(self):
        return self.cells.shape[:-1]

    def logical_cells(self) -> jax.Array:
        return self.cells + self.base[..., None].astype(self.cells.dtype)

    def sum(self) -> jax.Array:
        return clock_sum(self)


def zeros(m: int, k: int = 4, batch_shape: tuple = (), dtype=jnp.int32) -> BloomClock:
    return BloomClock(
        cells=jnp.zeros(batch_shape + (m,), dtype),
        base=jnp.zeros(batch_shape, dtype),
        k=k,
    )


def _as_mod_u32(x: jax.Array) -> jax.Array:
    """Reinterpret int32 counters as their position on the mod-2^32
    circle (uint32).  A wrapped counter (negative two's-complement bits)
    reads back as the large value it actually represents; sane values
    are unchanged."""
    if x.dtype == jnp.int32:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    return x


def clock_sum(c: BloomClock) -> jax.Array:
    """Total number of increments recorded (Σ cells + m·base), as float32.

    float32 because sums reach k × events and feed Eq. 3 exponents.
    int32 cells/bases are read through their mod-2^32 positions, so a
    near-wrap clock contributes its true (huge, Eq.3-saturating) sum
    instead of an int32-overflowed garbage value; in the sane range the
    result is bit-identical to a plain int32 sum.
    """
    s = jnp.sum(_as_mod_u32(c.cells), axis=-1).astype(jnp.float32)
    return s + _as_mod_u32(c.base).astype(jnp.float32) * c.m


def tick(c: BloomClock, event_hi, event_lo) -> BloomClock:
    """Record event(s): increment the k hashed cells per event.

    event_hi/lo: uint32 scalars or arrays whose shape is either
    ``c.batch_shape`` (one event per clock) or ``c.batch_shape + (E,)``
    (E events per clock).
    """
    event_hi = jnp.asarray(event_hi, jnp.uint32)
    event_lo = jnp.asarray(event_lo, jnp.uint32)
    idx = bloom_indices(event_hi, event_lo, c.k, c.m)  # [..., (E,) , k]
    # flatten any trailing event axes into one probe axis
    probe = idx.reshape(c.batch_shape + (-1,))
    one_hot = jax.nn.one_hot(probe, c.m, dtype=c.cells.dtype)  # [..., P, m]
    inc = jnp.sum(one_hot, axis=-2)
    return dataclasses.replace(c, cells=c.cells + inc)


def merge(a: BloomClock, b: BloomClock) -> BloomClock:
    """§3 step 3: element-wise max of logical cells.

    Keeps the max base and re-normalizes residuals so compression survives
    merging.  The max is derived from the wrap-subtraction
    ``a + relu(b - a)`` so a near-wrap clock merges correctly
    (bounded-counter semantics); in the sane range this is bit-identical
    to ``jnp.maximum``.
    """
    la = a.logical_cells()
    lb = b.logical_cells()
    mx = la + jnp.maximum(lb - la, 0)
    base = jnp.where(a.base - b.base >= 0, a.base, b.base)
    return BloomClock(cells=mx - base[..., None].astype(mx.dtype), base=base, k=a.k)


@dataclasses.dataclass(frozen=True)
class Ordering:
    """Result of comparing two clocks A, B.

    a_le_b / b_le_a: bool[...] cell-wise dominance each way.
    concurrent:      bool[...] neither dominates -> *exact* concurrency
                     (no false negatives, paper §3).
    equal:           bool[...] identical logical cells.
    fp_a_before_b:   float32[...] Eq. 3 false-positive rate of the claim
                     "A happened-before B" (valid where a_le_b).
    fp_b_before_a:   float32[...] symmetric.
    """

    a_le_b: jax.Array
    b_le_a: jax.Array
    concurrent: jax.Array
    equal: jax.Array
    fp_a_before_b: jax.Array
    fp_b_before_a: jax.Array


def fp_rate(sum_a, sum_b, m: int) -> jax.Array:
    """Paper Eq. 3: (1 - (1 - 1/m)^{ΣB})^{ΣA}, numerically stable.

    Valid under Eq. 4 (ΣB ≥ ΣA); callers pass sums either way and pick the
    branch via the dominance predicate.  Computed as
        exp(ΣA * log(-expm1(ΣB * log1p(-1/m))))
    so ΣB ~ 1e9 doesn't underflow pow.
    """
    sum_a = jnp.asarray(sum_a, jnp.float32)
    sum_b = jnp.asarray(sum_b, jnp.float32)
    log_q = jnp.log1p(-1.0 / m)          # log(1 - 1/m) < 0
    inner = -jnp.expm1(sum_b * log_q)    # 1 - (1-1/m)^ΣB  in (0, 1)
    inner = jnp.clip(inner, 1e-30, 1.0)
    return jnp.exp(sum_a * jnp.log(inner))


def ordering(a: BloomClock, b: BloomClock) -> Ordering:
    """Cell-wise partial-order comparison + Eq. 3 confidence, one pass.

    The algorithmic reference every kernel is validated against.  New
    code that wants accessor-style results should prefer
    ``repro.causal.compare`` (same math, typed ``Comparison`` pytree).
    """
    la = a.logical_cells()
    lb = b.logical_cells()
    # wrap-subtraction dominance (bounded-counter semantics): the signed
    # difference is exact whenever the true gap is < 2^31, so a clock
    # that wrapped past INT32_MAX still compares correctly; identical to
    # the direct <= in the sane range
    d = lb - la
    a_le_b = jnp.all(d >= 0, axis=-1)
    b_le_a = jnp.all(d <= 0, axis=-1)
    equal = jnp.logical_and(a_le_b, b_le_a)
    concurrent = jnp.logical_not(jnp.logical_or(a_le_b, b_le_a))
    sa = clock_sum(a)
    sb = clock_sum(b)
    return Ordering(
        a_le_b=a_le_b,
        b_le_a=b_le_a,
        concurrent=concurrent,
        equal=equal,
        fp_a_before_b=fp_rate(sa, sb, a.m),
        fp_b_before_a=fp_rate(sb, sa, a.m),
    )


def compress(c: BloomClock) -> BloomClock:
    """§4: lift min(cells) into the base so residuals stay small.

    [4,3,3,5,7,...] -> base+=3, cells=[1,0,0,2,4,...].  Happens naturally
    every ~m/k events; callers may apply it after every merge.

    The min is taken over wrap-differences from a reference cell so a
    window straddling the int32 wrap point (some cells wrapped negative,
    some not) still finds the true window floor; exact integer identity
    with the direct min in the sane range.
    """
    ref = c.cells[..., :1]
    mn = ref[..., 0] + jnp.min(c.cells - ref, axis=-1)
    return BloomClock(
        cells=c.cells - mn[..., None],
        base=c.base + mn.astype(c.base.dtype),
        k=c.k,
    )


def decompress(c: BloomClock) -> BloomClock:
    """Inverse of compress (materialize logical cells, zero base)."""
    return BloomClock(cells=c.logical_cells(), base=jnp.zeros_like(c.base), k=c.k)


def residual_span(c: BloomClock) -> jax.Array:
    """max - min of the residual cells: the §4 moving-window width.

    A clock whose span fits a byte ships / stores as u8 residuals plus
    one int32 base (see ``to_wire`` and ``repro.kernels.pack``).
    Wrap-safe: measured on differences from a reference cell, so a
    window straddling the int32 wrap point reports its true width.
    """
    d = c.cells - c.cells[..., :1]
    return jnp.max(d, axis=-1) - jnp.min(d, axis=-1)


def to_wire(c: BloomClock) -> dict:
    """Wire snapshot of one clock: §4 compression + u8 quantization.

    Applies ``compress`` then emits the residuals as uint8 whenever the
    window span fits a byte (the common case the paper argues for —
    ~4x smaller messages), falling back to int32 otherwise.  The dict is
    what gossip transports and checkpoint manifests persist.
    """
    cc = compress(c)
    cells = np.asarray(cc.cells)
    if cells.max(initial=0) <= 255:
        cells = cells.astype(np.uint8)
    return {"cells": cells, "base": int(cc.base), "k": cc.k}


def from_wire(snap) -> BloomClock:
    """Rebuild a clock from a ``to_wire`` dict (either cell dtype) or an
    encoded binary frame (``core.wire.encode_clock`` bytes, as shipped
    by the socket gossip transport).  Byte input is validated first —
    truncated / corrupted / unknown-version frames raise
    ``core.wire.WireFormatError`` instead of yielding a garbage clock.
    """
    if isinstance(snap, (bytes, bytearray, memoryview)):
        from repro.core import wire
        snap = wire.decode_clock(snap)
    return BloomClock(
        cells=jnp.asarray(snap["cells"], jnp.int32),
        base=jnp.asarray(int(snap["base"]), jnp.int32),
        k=int(snap["k"]),
    )


# ---------------------------------------------------------------------------
# convenience jitted entry points used across the runtime
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("threshold",))
def happened_before(a: BloomClock, b: BloomClock, threshold: float = 0.01):
    """True where "A -> B" holds with fp rate within ``threshold``.

    This is the decision rule the runtime uses (checkpoint lineage, async
    merge guards): dominance AND confidence — the same ``fp <= t`` gate
    as ``causal.Comparison.confident(t)`` and every registry/gossip
    admit path (an exact-boundary fp == t now passes, matching them).
    """
    o = ordering(a, b)
    return jnp.logical_and(o.a_le_b, o.fp_a_before_b <= threshold)


def comparability_matrix(clocks: BloomClock) -> dict[str, jax.Array]:
    """All-pairs comparison for a batch of clocks [n, m] -> n x n matrices.

    Used by the simulator and by fleet-level debugging dashboards.
    """
    n = clocks.cells.shape[0]
    ai = jax.tree.map(lambda x: x[:, None] if x.ndim == 1 else x[:, None, :], clocks)
    bi = jax.tree.map(lambda x: x[None, :] if x.ndim == 1 else x[None, :, :], clocks)
    ai = jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape[1:]), ai)
    bi = jax.tree.map(lambda x: jnp.broadcast_to(x, (n, n) + x.shape[2:]), bi)
    o = ordering(ai, bi)
    return {
        "a_le_b": o.a_le_b,
        "concurrent": o.concurrent,
        "fp": o.fp_a_before_b,
    }
