"""Vector clock baseline (paper §1.2) — the structure the bloom clock replaces.

Implemented with the same functional surface as ``repro.core.clock`` so the
simulator and the tests can swap the two and measure the §4 trade-offs
(space, comparability, exactness).  A vector clock is exact: comparisons
have no false positives, at O(N) space per message.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["VectorClock", "zeros", "tick", "merge", "compare"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class VectorClock:
    """vec: int32[..., n_nodes]."""

    vec: jax.Array

    def tree_flatten(self):
        return (self.vec,), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(leaves[0])

    @property
    def n(self) -> int:
        return self.vec.shape[-1]

    def sum(self) -> jax.Array:
        return jnp.sum(self.vec, axis=-1)


def zeros(n_nodes: int, batch_shape: tuple = (), dtype=jnp.int32) -> VectorClock:
    return VectorClock(jnp.zeros(batch_shape + (n_nodes,), dtype))


def tick(c: VectorClock, node_id) -> VectorClock:
    """§1.2 step 2: increment own slot."""
    one_hot = jax.nn.one_hot(node_id, c.n, dtype=c.vec.dtype)
    return VectorClock(c.vec + one_hot)


def merge(a: VectorClock, b: VectorClock) -> VectorClock:
    """§1.2 step 3 (without the local tick): element-wise max."""
    return VectorClock(jnp.maximum(a.vec, b.vec))


@dataclasses.dataclass(frozen=True)
class VCOrdering:
    a_le_b: jax.Array
    b_le_a: jax.Array
    concurrent: jax.Array
    equal: jax.Array


def compare(a: VectorClock, b: VectorClock) -> VCOrdering:
    a_le_b = jnp.all(a.vec <= b.vec, axis=-1)
    b_le_a = jnp.all(b.vec <= a.vec, axis=-1)
    return VCOrdering(
        a_le_b=a_le_b,
        b_le_a=b_le_a,
        concurrent=jnp.logical_not(jnp.logical_or(a_le_b, b_le_a)),
        equal=jnp.logical_and(a_le_b, b_le_a),
    )


def wire_bytes(n_nodes: int, counter_bytes: int = 4) -> int:
    """Message size of a vector clock (§2: O(N))."""
    return n_nodes * counter_bytes
