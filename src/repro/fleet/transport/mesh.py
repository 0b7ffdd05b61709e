"""MeshCollectiveTransport: digest exchange as a ppermute ring.

A mesh-sharded ``ClockRegistry`` already holds the fleet's rows as
``[N/d, m]`` per-device shards, and its classify / all-pairs kernels
run shard_map'd — a session over it needs no host-side row movement at
all.  What a round DOES need fleet-wide is the digest view (clock sums,
liveness, §4 bases) of every shard.  This transport runs that exchange
as a ``d-1``-hop ``ppermute`` ring over the fleet axis — each device
circulates its digest shard around the ring and assembles the
replicated full vectors on device, exactly like the all-pairs block-row
ring — then lands the result on host in ONE transfer.  Row shards
themselves never round-trip through the host: deltas don't exist
(the slab is authoritative) and push-back is the registry's batched
scatter, which XLA routes to each row's owning shard.

``digest_bytes`` reports the measured per-node inbound ring traffic:
``(d - 1)`` hops of one digest shard (f32 sum + bool alive + i32 base
per slot).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import wire
from repro.fleet.transport.base import Transport

__all__ = ["MeshCollectiveTransport"]


@functools.lru_cache(maxsize=16)
def _digest_ring_fn(mesh, axis: str):
    """Jitted shard_map'd digest all-gather: each device walks its
    (sums, alive, base) shard around the ring and every device returns
    the replicated full vectors.  Cached per (mesh, axis) so repeated
    sessions reuse the compiled ring."""
    d = mesh.shape[axis]

    def ring(sums, alive, base):
        nd = sums.shape[0]
        my = jax.lax.axis_index(axis)
        out_s = jnp.zeros((d * nd,), sums.dtype)
        out_a = jnp.zeros((d * nd,), alive.dtype)
        out_b = jnp.zeros((d * nd,), base.dtype)
        cs, ca, cb = sums, alive, base
        shift = [(i, (i + 1) % d) for i in range(d)]
        for h in range(d):
            if h:
                cs = jax.lax.ppermute(cs, axis, shift)
                ca = jax.lax.ppermute(ca, axis, shift)
                cb = jax.lax.ppermute(cb, axis, shift)
            src = (my - h) % d          # shard visiting this device now
            out_s = jax.lax.dynamic_update_slice(out_s, cs, (src * nd,))
            out_a = jax.lax.dynamic_update_slice(out_a, ca, (src * nd,))
            out_b = jax.lax.dynamic_update_slice(out_b, cb, (src * nd,))
        return out_s, out_a, out_b

    return jax.jit(jax.shard_map(
        ring, mesh=mesh,
        in_specs=(P(axis),) * 3,
        out_specs=(P(),) * 3,
        check_vma=False,     # replication holds by construction (full ring)
    ))


class MeshCollectiveTransport(Transport):
    name = "mesh"
    authoritative = True

    def __init__(self, registry):
        super().__init__()
        if registry.mesh is None:
            raise ValueError(
                "MeshCollectiveTransport needs a mesh-sharded registry "
                "(ClockRegistry(..., mesh=make_fleet_mesh(...)))")
        self.registry = registry
        self._ring = _digest_ring_fn(registry.mesh, registry.axis)

    def digests(self) -> tuple[dict, int]:
        """Run the per-round digest exchange (the ring collective) and
        return the observer's replicated fleet view.

        The session itself only needs the exchange to have happened (the
        slab is authoritative, nothing is ingested); the digest dict is
        the host-side fleet view for callers above the session —
        dashboards, convergence checks, tests pinning ring-vs-slab
        agreement.  ``digest_bytes`` is derived from the vectors the
        ring actually circulated: each of the ``d - 1`` hops delivers
        one foreign shard of every vector to this node.
        """
        self._begin_round()
        r = self.registry
        sums, alive, base = jax.device_get(
            self._ring(r.sums, r.alive, r.base))
        slot_to_pid = {s: pid for pid, s in r._slot_of.items()}
        digs = {}
        for slot in np.flatnonzero(alive):
            pid = slot_to_pid.get(int(slot))
            if pid is None:
                continue          # evicted between scatter and ring
            # crc=0: content keys are never consulted on an
            # authoritative fabric — cells stay sharded on device
            digs[pid] = wire.ClockDigest(
                peer_id=str(pid), clock_sum=float(sums[slot]),
                base=int(base[slot]), m=r.m, k=r.k, crc=0)
        d = r.n_shards
        ring_bytes = (sum(v.nbytes for v in (sums, alive, base))
                      * (d - 1) // d)
        return digs, ring_bytes

    def pull(self, peer_ids) -> tuple[dict[str, bytes], int]:
        return {}, 0              # the sharded slab is authoritative

    def push(self, peer_ids, frame: bytes) -> int:
        # delivery is the session's registry.broadcast — one batched
        # scatter XLA routes to each accepted row's owning shard
        return len(frame) * len(peer_ids)
