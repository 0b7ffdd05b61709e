"""ClockRegistry: a fixed-capacity quantized slab of peer bloom clocks.

The registry is the fleet-scale replacement for holding one
``BloomClock`` object per peer and comparing them one ``bool()`` at a
time.  Peer state lives in four device arrays — the §4 packed layout
(see ``repro.kernels.pack``):

    cells_u8 [N, m] uint8  window-relative residuals per slot
    base     [N]    int32  per-slot window offset (logical = base + u8)
    sums     [N]    f32    cached total increments (Eq. 3 inputs)
    alive    [N]    bool   liveness mask (evicted slots stay allocated)

u8 residuals cut slab memory and every kernel's HBM traffic 4x versus
the old int32 slab.  A row whose residual span cannot fit a byte is
**automatically promoted**: its int32 logical cells go to a small host
side-store and all bulk operations transparently fall back to a
materialized int32 slab until the row is overwritten with packable data
(or evicted).  Scatter, union and broadcast operate directly on
(u8, base) — no int32 round-trip on the packed path.

Slot assignment is host-side (a dict + free list); everything that
touches cell data is batched: ``admit_many`` / ``update_many`` are one
scatter each, and all classification goes through the ONE dispatch
front-door — ``repro.causal.CausalEngine`` — built from the registry's
``CausalPolicy``: ``classify_all`` is ``engine.classify`` over the
packed slab (one device call), ``all_pairs`` is ``engine.pairs`` with
the alive mask (dead slots cost no work and report all-False flags;
promoted rows get the exact int32 rim inside the engine).

Status codes (``FleetView.status``) are small ints so a whole fleet's
classification is a single int8 vector:

    DEAD < 0: slot empty/evicted;  ANCESTOR: peer ≼ local;
    SAME: equal;  DESCENDANT: local ≼ peer;  FORKED: concurrent
    (exact — no false negatives, paper §3).

**Sharded mode** (``ClockRegistry(..., mesh=mesh, axis="fleet")``): the
slab arrays carry a row-sharded ``NamedSharding`` over one mesh axis —
``cells_u8`` lives as ``[N/d, m]`` per-device shards so a fleet can
outgrow any single device's memory.  ``classify_all`` becomes a
``shard_map``'d one-vs-many kernel (query replicated, zero cross-device
traffic) and ``all_pairs`` a block-row ring: each device circulates a
column shard via ``ppermute`` and fills its ``[N/d, N]`` block-row with
the packed full-rect engine.  Both paths are bit-identical to the
single-device packed engines for every shard count — the multi-device
harness (``tests/test_sharded_fleet.py``) enforces it.  Mutations
(admit / evict / update / union / broadcast) stay one batched device
call; XLA routes each scattered row to its owning shard and the result
is re-placed onto the registry's sharding.  Slot assignment remains a
host-side dict, so slot ``s`` deterministically lives on device
``s // (N / d)``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.causal import CausalEngine, CausalPolicy, PackedSlab
from repro.core import clock as bc
from repro.core import wire
from repro.kernels import pack
from repro.obs.observer import resolve
from repro.sharding import FLEET_AXIS, slab_shardings

__all__ = [
    "ClockRegistry",
    "EvictedRow",
    "FleetView",
    "view_from_classify",
    "DEAD",
    "ANCESTOR",
    "SAME",
    "DESCENDANT",
    "FORKED",
    "STATUS_NAMES",
    "NEAR_WRAP_MARGIN",
]

INT32_MAX = np.iinfo(np.int32).max

#: a row whose §4 base lands within this margin of INT32_MAX (or has
#: already wrapped negative) is routed through promotion — the exact
#: int32 rim compares with wrap-subtraction, so near-wrap rows can
#: never produce an inverted le/ge bit through the packed fast path,
#: whose in-kernel f32 sums would overflow first.  2^20 leaves room
#: for ~a million more ticks plus the u8 residual window.
NEAR_WRAP_MARGIN = 1 << 20


def _near_wrap(base: np.ndarray) -> np.ndarray:
    """Bool mask of §4 bases too close to (or past) the int32 wrap."""
    base = np.asarray(base, np.int64)
    return (base > INT32_MAX - NEAR_WRAP_MARGIN) | (base < 0)


def _pow2_bucket(n: int, cap: int | None = None) -> int:
    """Next power of two ≥ n: batched mutations pad to these buckets so
    the compiled scatter/gather shape count stays logarithmic under
    churny variable-size admit/evict waves.  ``cap`` (the slab
    capacity) clamps the bucket: a batch one past a pow2 boundary must
    not pad beyond the slab and rely on downstream crop — there are no
    valid slots to alias the padding to past capacity."""
    bucket = 1 << max(0, n - 1).bit_length() if n > 1 else n
    return bucket if cap is None else min(bucket, cap)

DEAD = -1
ANCESTOR = 0
SAME = 1
DESCENDANT = 2
FORKED = 3

STATUS_NAMES = {
    DEAD: "dead",
    ANCESTOR: "ancestor",
    SAME: "same",
    DESCENDANT: "descendant",
    FORKED: "forked",
}


@dataclasses.dataclass
class EvictedRow:
    """One row captured for an ``on_evict`` hook, in the slab's own
    packed representation: u8 residuals + base (plus the promoted int32
    logical row when the slot was wide).  A tiered store (see
    ``repro.serve.tiers``) ingests these directly — the demotion path
    never materializes the full slab."""

    cells_u8: np.ndarray      # [m] uint8 residuals
    base: int                 # §4 window offset
    sum: float                # cached clock sum (Eq. 3 input)
    wide: Optional[np.ndarray] = None   # promoted int32 logical row

    def logical(self) -> np.ndarray:
        """Materialized int32 logical cells (mod-2^32 circle)."""
        if self.wide is not None:
            return np.asarray(self.wide, np.int32)
        return (self.cells_u8.astype(np.int64)
                + int(self.base)).astype(np.int32)


@dataclasses.dataclass
class FleetView:
    """Host-side result of one ``classify_all`` call (numpy, [capacity])."""

    status: np.ndarray        # int8 status code per slot
    fp: np.ndarray            # float32 Eq. 3 fp of the claimed direction
    sums: np.ndarray          # float32 cached clock sums
    alive: np.ndarray         # bool liveness mask
    local_sum: float          # the query clock's total increments
    engine: str = ""          # dispatch label that produced this view

    def slots(self, code: int) -> np.ndarray:
        return np.flatnonzero(self.status == code)

    def counts(self) -> dict[str, int]:
        return {
            name: int(np.sum(self.status == code))
            for code, name in STATUS_NAMES.items()
        }

    def confident(self, threshold: float) -> np.ndarray:
        """The uniform Eq. 3 gate over the claimed direction, mirroring
        ``causal.ClassifyResult.confident`` (exact verdicts — SAME,
        FORKED, DEAD — carry fp 0 and are always confident)."""
        return self.fp <= threshold


def _fold(xp, q_le_p, p_le_q, fp_q_before_p, fp_p_before_q, alive):
    """Classify flags -> (status int8, claimed-direction fp float32).

    One elementwise select chain for numpy (``xp=np``) and ``jnp``
    alike: FORKED by default, ANCESTOR where the peer ≼ local,
    DESCENDANT where local ≼ peer overrides it, SAME where both hold,
    DEAD where the slot is not alive.  fp is the claimed direction's
    Eq. 3 value as ``ClassifyResult.claimed_fp`` selects it; SAME,
    FORKED and DEAD are exact and carry 0.  No arithmetic: every output
    bit is an input bit."""
    status = xp.where(p_le_q, ANCESTOR, FORKED)
    status = xp.where(q_le_p, DESCENDANT, status)
    status = xp.where(q_le_p & p_le_q, SAME, status)
    status = xp.where(alive, status, DEAD).astype(np.int8)
    fp = xp.where(p_le_q, fp_p_before_q, fp_q_before_p)
    fp = xp.where(alive & (q_le_p != p_le_q), fp, 0.0).astype(np.float32)
    return status, fp


@jax.jit
def _fold_on_device(q_le_p, p_le_q, fp_q_before_p, fp_p_before_q, alive):
    return _fold(jnp, q_le_p, p_le_q, fp_q_before_p, fp_p_before_q, alive)


def view_from_classify(res, alive: np.ndarray, capacity: int,
                       local_sum: float | None = None, *,
                       alive_dev: jax.Array | None = None,
                       obs=None) -> FleetView:
    """Fold a ``ClassifyResult`` into a host-side ``FleetView``.

    The ONE place classify flags become status codes + claimed-direction
    fp — ``ClockRegistry.classify_all`` and the tiered registry
    (``repro.serve.tiers``) both route through it, so a tier split can
    never drift from the flat slab's verdict semantics.

    Where the fold runs follows from where ``res`` lives: a device
    result is folded on the device (``alive_dev``, or ``alive``
    uploaded) and only status, fp and the sums are read back — 9 bytes
    a row plus the query's sum; a host result (numpy leaves) is folded
    in numpy.  ``capacity`` is the view's length, the result's row
    count.  Span ``registry.fold`` covers the fold, with
    ``registry.readback`` inside it around the transfer; counters
    ``registry_fold{where=device|host}`` and ``registry_readback_bytes``.
    """
    obs = resolve(obs)
    alive = np.asarray(alive, bool)
    flags = (res.q_le_p, res.p_le_q, res.fp_q_before_p, res.fp_p_before_q)
    where = "device" if isinstance(res.q_le_p, jax.Array) else "host"
    with obs.trace.span("registry.fold"):
        if where == "device":
            folded = _fold_on_device(
                *flags, alive if alive_dev is None else alive_dev)
            with obs.trace.span("registry.readback"):
                host = jax.device_get((*folded, res.sum_p, res.sum_q))
            if obs.metrics:
                obs.metrics.counter("registry_readback_bytes").inc(
                    sum(x.nbytes for x in host))
            status, fp, sums, sum_q = host
        else:
            status, fp = _fold(np, *flags, alive)
            sums, sum_q = res.sum_p, res.sum_q
        obs.metrics.counter("registry_fold", where=where).inc()
        return FleetView(
            status=status,
            fp=fp,
            sums=sums,
            alive=alive.copy(),
            local_sum=float(sum_q) if local_sum is None else local_sum,
            engine=res.engine or "",
        )


@jax.jit
def _scatter_rows(cells_u8, base, sums, alive, idx, new_u8, new_base, new_sums):
    cells_u8 = cells_u8.at[idx].set(new_u8)
    base = base.at[idx].set(new_base)
    sums = sums.at[idx].set(new_sums)
    alive = alive.at[idx].set(True)
    return cells_u8, base, sums, alive


@jax.jit
def _union_rows_packed(cells_u8, base, mask, local_cells):
    """max(local, max over masked logical rows); the widen fuses with the
    reduce, so the only slab read is the u8 residuals.  The max is the
    wrap-safe ``local + relu(row - local)`` derivation (bounded-counter
    semantics) — bit-identical to a direct maximum in the sane range,
    correct when a row's base has wrapped past INT32_MAX."""
    logical = cells_u8.astype(jnp.int32) + base[:, None]
    gain = jnp.where(mask[:, None],
                     jnp.maximum(logical - local_cells, 0), 0)
    return local_cells + jnp.max(gain, axis=0)


@jax.jit
def _broadcast_rows(cells_u8, base, sums, mask, row_u8, row_base, row_sum):
    cells_u8 = jnp.where(mask[:, None], row_u8[None, :], cells_u8)
    base = jnp.where(mask, row_base, base)
    sums = jnp.where(mask, row_sum, sums)
    return cells_u8, base, sums


@jax.jit
def _materialize(cells_u8, base):
    return pack.unpack_rows(cells_u8, base)


class ClockRegistry:
    """Peer clock registry: one device slab, or mesh-sharded row shards."""

    def __init__(self, capacity: int, m: int, k: int = 4, *,
                 mesh=None, axis: str = FLEET_AXIS,
                 policy: CausalPolicy | None = None):
        self.capacity = capacity
        self.m = m
        self.k = k
        # the CausalPolicy is the one source of truth for dispatch: the
        # mesh/axis arguments fold into it (explicit args win so the
        # pre-policy constructor signature keeps working), and every
        # comparison below goes through the resulting CausalEngine
        base_policy = policy if policy is not None else CausalPolicy()
        if mesh is None:
            mesh = base_policy.mesh
            if mesh is not None and axis == FLEET_AXIS:
                axis = base_policy.axis
        self.policy = dataclasses.replace(base_policy, mesh=mesh, axis=axis)
        self.engine = CausalEngine(self.policy)
        self.obs = resolve(getattr(self.policy, "observer", None))
        self.mesh = mesh
        self.axis = axis if mesh is not None else None
        if mesh is not None:
            shards = mesh.shape[axis]
            if capacity % shards:
                raise ValueError(
                    f"capacity {capacity} not divisible by mesh axis "
                    f"{axis!r} extent {shards}")
            self._slab_sharding, self._vec_sharding = slab_shardings(
                mesh, axis)
        else:
            self._slab_sharding = self._vec_sharding = None
        self.cells_u8 = self._place2d(jnp.zeros((capacity, m), jnp.uint8))
        self.base = self._place1d(jnp.zeros((capacity,), jnp.int32))
        self.sums = self._place1d(jnp.zeros((capacity,), jnp.float32))
        self.alive = self._place1d(jnp.zeros((capacity,), bool))
        self._alive_host = np.zeros(capacity, bool)
        self._base_host = np.zeros(capacity, np.int64)
        # per-slot CRC32 of the logical cells, written at every mutation:
        # the ground truth check_integrity() verifies the slab against
        # (corruption detection on admit/union, repaired via gossip)
        self._crc_host = np.zeros(capacity, np.int64)
        self._wide: dict[int, np.ndarray] = {}   # promoted int32 rows
        self._mat: jax.Array | None = None       # materialized i32 cache
        self._slot_of: dict = {}
        self._free: list[int] = list(range(capacity - 1, -1, -1))
        #: demotion hook: called as ``on_evict({peer_id: EvictedRow})``
        #: with every ALIVE row an ``evict_many`` is about to free —
        #: quarantined (corrupt) rows are never handed out.  A tiered
        #: store installs this to catch hot-tier evictions (see
        #: ``repro.serve.tiers``).
        self.on_evict: Optional[Callable[[dict], None]] = None

    @property
    def n_shards(self) -> int:
        return 1 if self.mesh is None else self.mesh.shape[self.axis]

    def _place2d(self, x: jax.Array) -> jax.Array:
        """Pin a [N, m] slab to the registry's row sharding (no-op when
        unsharded).  Every mutation re-places its result so XLA's output
        placement choices never silently gather the slab."""
        return x if self._slab_sharding is None else jax.device_put(
            x, self._slab_sharding)

    def _place1d(self, x: jax.Array) -> jax.Array:
        return x if self._vec_sharding is None else jax.device_put(
            x, self._vec_sharding)

    # ---- membership ----
    def __len__(self) -> int:
        return len(self._slot_of)

    def __contains__(self, peer_id) -> bool:
        return peer_id in self._slot_of

    def slot_of(self, peer_id) -> int:
        return self._slot_of[peer_id]

    def peer_ids(self) -> list:
        return list(self._slot_of)

    def row_alive(self, peer_id) -> bool:
        """True when the peer's row is present AND not quarantined."""
        slot = self._slot_of.get(peer_id)
        return slot is not None and bool(self._alive_host[slot])

    @property
    def packed(self) -> bool:
        """True when every row is in the u8 fast-path representation."""
        return not self._wide

    @property
    def cells(self) -> jax.Array:
        """Materialized int32 logical cells (back-compat / debug view)."""
        return self._materialized()

    def _materialized(self) -> jax.Array:
        if self._mat is None:
            mat = _materialize(self.cells_u8, self.base)
            if self._wide:
                idx = jnp.asarray(sorted(self._wide), jnp.int32)
                rows = jnp.asarray(
                    np.stack([self._wide[s] for s in sorted(self._wide)]))
                mat = mat.at[idx].set(rows)
            self._mat = mat
        return self._mat

    def _slab(self) -> PackedSlab:
        """The engine-facing view of the slab arrays (wide rows and the
        host base copy ride along so the front-door can overlay promoted
        rows and probe base uniformity without device syncs)."""
        return PackedSlab(self.cells_u8, self.base,
                          base_host=self._base_host, wide=self._wide)

    # ---- batched mutation ----
    def admit_many(self, peers: dict) -> dict:
        """Admit {peer_id: BloomClock}; one scatter for the whole batch.

        Re-admitting a known peer_id overwrites its row (re-spawned
        peers keep their slot).  Returns {peer_id: slot}.  Raises when
        capacity is exhausted.
        """
        if not peers:
            return {}
        fresh = [pid for pid in peers if pid not in self._slot_of]
        if len(fresh) > len(self._free):
            raise RuntimeError(
                f"registry full: {len(fresh)} admits, {len(self._free)} free slots")
        with self.obs.trace.span("registry.admit", n=len(peers),
                                 fresh=len(fresh)):
            slots = {pid: (self._slot_of[pid] if pid in self._slot_of
                           else self._free.pop()) for pid in peers}
            self._slot_of.update(slots)
            self._write(list(slots.values()), list(peers.values()))
        self.obs.metrics.counter("registry_admits").inc(len(peers))
        self._note_occupancy()
        return slots

    def admit(self, peer_id, clock: bc.BloomClock) -> int:
        return self.admit_many({peer_id: clock})[peer_id]

    def update_many(self, peers: dict) -> None:
        """Overwrite existing peers' rows; one scatter for the batch."""
        if not peers:
            return
        with self.obs.trace.span("registry.update", n=len(peers)):
            self._write([self._slot_of[pid] for pid in peers],
                        list(peers.values()))

    def update(self, peer_id, clock: bc.BloomClock) -> None:
        self.update_many({peer_id: clock})

    def evict_many(self, peer_ids) -> None:
        peer_ids = list(dict.fromkeys(peer_ids))   # dedupe, keep order
        # resolve every slot BEFORE mutating: an unknown peer_id raises
        # with the registry untouched instead of half-evicted
        idx = [self._slot_of[pid] for pid in peer_ids]
        if not idx:
            return
        captured = self._capture_rows(peer_ids, idx)
        with self.obs.trace.span("registry.evict", n=len(idx)):
            for pid in peer_ids:
                del self._slot_of[pid]
            pidx = idx + [idx[-1]] * (_pow2_bucket(len(idx), self.capacity) - len(idx))
            self.alive = self._place1d(
                self.alive.at[jnp.asarray(pidx)].set(False))
            self._alive_host[idx] = False
            for slot in idx:
                self._wide.pop(slot, None)
            self._free.extend(idx)
        self.obs.metrics.counter("registry_evictions").inc(len(idx))
        self._note_occupancy()
        if captured:
            self.on_evict(captured)

    def _capture_rows(self, peer_ids: list, idx: list) -> Optional[dict]:
        """Snapshot the alive rows an eviction is about to free, in the
        packed representation (one gathered device transfer for the
        batch, not a full-slab materialize)."""
        if self.on_evict is None:
            return None
        live = [(pid, slot) for pid, slot in zip(peer_ids, idx)
                if self._alive_host[slot]]
        if not live:
            return None
        slots = [slot for _, slot in live]
        slots += [slots[-1]] * (_pow2_bucket(len(slots), self.capacity)
                                - len(slots))
        jidx = jnp.asarray(slots)
        u8 = np.asarray(jnp.take(self.cells_u8, jidx, axis=0))
        sums = np.asarray(jnp.take(self.sums, jidx))
        return {
            pid: EvictedRow(
                cells_u8=u8[pos].copy(),
                base=int(self._base_host[slot]),
                sum=float(sums[pos]),
                wide=(None if slot not in self._wide
                      else self._wide[slot].copy()))
            for pos, (pid, slot) in enumerate(live)
        }

    def evict(self, peer_id) -> None:
        self.evict_many([peer_id])

    def _write(self, idx: list, clocks: list) -> None:
        # materialize logical rows host-side (int32 wraparound kept via
        # the mod-2^32 fold) and sum them in ONE batched op: per-clock
        # eager dispatches dominate bulk admits otherwise
        n0 = len(clocks)
        n = _pow2_bucket(n0, self.capacity)
        logical_h = np.empty((n, self.m), np.int32)
        for pos, c in enumerate(clocks):
            cells = np.asarray(c.cells, np.int64)
            b = int(np.asarray(c.base))
            logical_h[pos] = ((cells + b) & 0xFFFFFFFF).astype(
                np.uint32).view(np.int32)
        if n > n0:
            # pad to a power-of-two bucket by repeating the last row at
            # its own slot — the duplicate scatter rewrites identical
            # data, and the compiled shape count stays logarithmic
            logical_h[n0:] = logical_h[n0 - 1]
            idx = list(idx) + [idx[-1]] * (n - n0)
        logical = jnp.asarray(logical_h)
        new_sums = bc.clock_sum(bc.BloomClock(
            cells=logical, base=jnp.zeros(n, jnp.int32),
            k=clocks[0].k))
        new_u8, new_base, ok = pack.pack_rows(logical)
        cells_u8, base, sums, alive = _scatter_rows(
            self.cells_u8, self.base, self.sums, self.alive,
            jnp.asarray(idx), new_u8, new_base, new_sums)
        self.cells_u8 = self._place2d(cells_u8)
        self.base = self._place1d(base)
        self.sums = self._place1d(sums)
        self.alive = self._place1d(alive)
        ok_h = np.asarray(ok)
        base_h = np.asarray(new_base)
        # near-wrap guard: a base within NEAR_WRAP_MARGIN of INT32_MAX
        # (or already wrapped) rides the exact int32 rim via promotion —
        # the packed path's in-kernel sums are not wrap-safe
        nw_h = _near_wrap(base_h)
        self._base_host[idx] = base_h
        self._alive_host[idx] = True
        promoted = demoted = 0
        for pos, slot in enumerate(idx):
            self._crc_host[slot] = wire.cells_crc(logical_h[pos])
            if ok_h[pos] and not nw_h[pos]:
                if self._wide.pop(slot, None) is not None:
                    demoted += 1               # demotion: row packs again
            else:                  # promotion: span > U8_MAX or near-wrap
                if slot not in self._wide:
                    promoted += 1
                self._wide[slot] = logical_h[pos].copy()
        if promoted:
            self.obs.metrics.counter("registry_promotions").inc(promoted)
        if demoted:
            self.obs.metrics.counter("registry_demotions").inc(demoted)
        self._mat = None

    def _note_occupancy(self) -> None:
        obs = self.obs
        if obs:
            obs.metrics.gauge("registry_occupancy").set(len(self._slot_of))
            obs.metrics.gauge("registry_wide_rows").set(len(self._wide))

    # ---- self-stabilization: row integrity ----
    def check_integrity(self) -> list:
        """Verify every alive row against the CRC recorded when it was
        written; returns the peer ids whose slab state no longer hashes
        to it (bit rot, a bad scatter, hostile mutation).

        The CRC is over the canonical logical cells
        (``core.wire.cells_crc``), so packed and promoted rows verify
        identically.  Detection only — callers quarantine and repair
        via :meth:`quarantine_rows` + the gossip delta pull (the session
        protocol does both when ``GossipConfig.verify_rows`` is set).
        """
        mat = np.asarray(self._materialized())
        bad = []
        for pid, slot in self._slot_of.items():
            if not self._alive_host[slot]:
                continue
            if wire.cells_crc(mat[slot]) != int(self._crc_host[slot]):
                bad.append(pid)
        if bad:
            self.obs.metrics.counter("registry_corrupt_rows").inc(len(bad))
        return bad

    def quarantine_rows(self, peer_ids) -> None:
        """Mark corrupted rows dead WITHOUT freeing their slots: the
        peer stays known (``slot_of`` keeps resolving) but classify /
        union / all_pairs ignore the poisoned cells.  A subsequent
        ``update_many`` — e.g. the session's forced delta re-pull from
        any peer whose digest covers the row — rewrites the row, marks
        it alive again, and refreshes its CRC."""
        idx = [self._slot_of[pid] for pid in peer_ids]
        if not idx:
            return
        self.alive = self._place1d(
            self.alive.at[jnp.asarray(idx)].set(False))
        self._alive_host[idx] = False
        self._mat = None

    def get(self, peer_id) -> bc.BloomClock:
        slot = self._slot_of[peer_id]
        if slot in self._wide:
            return bc.BloomClock(cells=jnp.asarray(self._wide[slot]),
                                 base=jnp.zeros((), jnp.int32), k=self.k)
        return bc.BloomClock(cells=self.cells_u8[slot].astype(jnp.int32),
                             base=self.base[slot], k=self.k)

    # ---- batched classification ----
    def classify_all(self, local: bc.BloomClock) -> FleetView:
        """Lineage status + Eq. 3 fp for EVERY slot in one device call.

        Direction convention matches ``ClockRuntime.lineage``: a peer
        that is ≼ the local clock is an ANCESTOR (its events are in the
        local past), a peer the local clock is ≼ is a DESCENDANT, and
        incomparable peers are FORKED (exact, §3).

        One ``engine.classify`` call: the front-door runs the packed
        one-vs-many kernel (shard_map'd over the row shards when the
        policy carries a mesh) and overlays promoted rows through the
        exact int32 kernel — the bulk never drops to the fallback.

        Spans: ``registry.classify_all`` around the call, and inside it
        ``causal.classify`` (host dispatch) and ``registry.fold`` (the
        fold's dispatch on the device and the host-side wrap), which
        holds ``registry.readback`` (the wait for the device and the
        transfer of status, fp and sums: 9 bytes a row plus 4); the
        counter ``registry_readback_bytes`` sums the bytes read back.
        """
        obs = self.obs
        with obs.trace.span("registry.classify_all",
                            n=self.capacity) as span:
            res = self.engine.classify(local, self._slab())
            span.set(engine=res.engine)
            return view_from_classify(res, self._alive_host, self.capacity,
                                      alive_dev=self.alive, obs=obs)

    def all_pairs(self, **kw):
        """Tiled all-pairs compare -> ``causal.ComparisonMatrix`` (also
        answers the legacy dict keys); dead slots report all-False flags
        and ``fp = row_sums = 0`` — no misleading verdicts from stale
        cells.

        One ``engine.pairs`` call over the packed slab: the front-door
        alive-compacts unsharded fleets (dead slots cost no compute),
        runs the block-row ``ppermute`` ring and masks dead slots on
        device for sharded ones, and patches promoted rows through the
        exact int32 rim in both modes.  ``**kw`` carries per-call
        dispatch overrides (engine / block shapes / interpret).
        """
        return self.engine.pairs(self._slab(), alive=self._alive_host,
                                 alive_dev=self.alive, **kw)

    # ---- batched merge ----
    def union(self, mask: np.ndarray, local: bc.BloomClock) -> bc.BloomClock:
        """Merge the local clock with every masked row (one device call).

        With promoted rows present, only the MASKED rows are gathered
        and unpacked (plus the promoted handful patched in wide) — the
        full slab is never materialized int32, so a sharded fleet's
        gossip round stays within its per-device memory bound.
        """
        local_cells = local.logical_cells().astype(jnp.int32)
        mask_h = np.asarray(mask, bool)
        midx = np.flatnonzero(mask_h)
        if midx.size == 0:
            return bc.BloomClock(
                cells=local_cells, base=jnp.zeros((), jnp.int32), k=self.k)
        if self.packed:
            merged = _union_rows_packed(
                self.cells_u8, self.base, jnp.asarray(mask_h), local_cells)
        else:
            jmid = jnp.asarray(midx)
            rows = pack.unpack_rows(
                jnp.take(self.cells_u8, jmid, axis=0),
                jnp.take(self.base, jmid))
            wsel = [(pos, int(s)) for pos, s in enumerate(midx)
                    if int(s) in self._wide]
            if wsel:
                rows = rows.at[jnp.asarray([p for p, _ in wsel])].set(
                    jnp.asarray(np.stack([self._wide[s] for _, s in wsel])))
            # wrap-safe max (same derivation as _union_rows_packed)
            merged = local_cells + jnp.maximum(
                jnp.max(rows - local_cells, axis=0), 0)
        return bc.BloomClock(
            cells=merged, base=jnp.zeros((), jnp.int32), k=self.k)

    def broadcast(self, mask: np.ndarray, clock: bc.BloomClock) -> bool:
        """Write one clock into every masked row (anti-entropy push-back).

        The row ships in wire form: u8 residuals + one base scalar
        (§4 compression), 4x less traffic than an int32 row.  A row too
        wide for u8 promotes the masked slots instead.  Returns whether
        the row went out packed (False = int32 promoted-row fallback).
        """
        logical = clock.logical_cells().astype(jnp.int32)
        row_u8, row_base, ok = pack.pack_rows(logical[None])
        row_sum = bc.clock_sum(clock)
        mask_d = jnp.asarray(mask, bool)
        cells_u8, base, sums = _broadcast_rows(
            self.cells_u8, self.base, self.sums, mask_d,
            row_u8[0], row_base[0], row_sum)
        self.cells_u8 = self._place2d(cells_u8)
        self.base = self._place1d(base)
        self.sums = self._place1d(sums)
        midx = np.flatnonzero(np.asarray(mask))
        self._base_host[midx] = int(row_base[0])
        row_np = np.asarray(logical)
        self._crc_host[midx] = wire.cells_crc(row_np)
        # same near-wrap guard as _write: a union row pushed back near
        # the int32 wrap stays on the exact rim
        packed_ok = bool(ok[0]) and not bool(_near_wrap(
            np.asarray([int(row_base[0])]))[0])
        if packed_ok:
            for slot in midx:
                self._wide.pop(int(slot), None)
        else:
            for slot in midx:
                self._wide[int(slot)] = row_np
        self._mat = None
        return packed_ok


