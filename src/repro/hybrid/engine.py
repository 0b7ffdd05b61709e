"""HybridEngine: exact clocks for the hot set over a packed bloom tail.

The serving population is Zipf-skewed: a small hot set absorbs most
classifies while the long tail sits cold.  Every session here is
described EXACTLY by a cheap host-side catalog entry — a prefix length
``v`` into the local event chain plus a handful of private event ids —
and the engine chooses a *representation* per session, not just a
placement (generalizing the tiers' promoted-row int32 overlay):

  hot   the catalog entry itself, shipped to the device as an
        ``[H, 2] (v, n_private)`` row.  Verdicts against the local
        chain at version ``V`` are exact set containment —
        ``query ≼ peer  ⟺  V ≤ v`` and ``peer ≼ query  ⟺  v ≤ V and
        n_private == 0`` — so the claimed AND measured fp is zero,
        and no O(m) cells are read at all;
  tail  the §4 packed bloom row (u8 residuals + i32 base, int32 wide
        rows on the side dict) minted deterministically from the same
        catalog entry, compared by the usual Eq. 3 bloom math.

One ``classify()`` fuses both paths through the generated ``hybrid``
kernel topology (``kernels.template``): hot row-tiles and tail
row-tiles share one grid, so hot rows never fall back to host loops.
Tail verdicts are bit-identical to a flat packed slab at the same
block shapes; hot verdicts come back with fp ≡ 0.0.

Because minting is deterministic (double-hash probes mod m) and probe
indices fold exactly across power-of-two geometry changes
(``(x mod m) mod m' == x mod m'`` when ``m' | m``), demotion re-mints
bit-identically and ``resize_tail`` folds every live row — plus the
local chain — to a smaller ``m`` with per-row audit records that
replay bit-for-bit (``hybrid.adaptive.replay_resize``).

Promotion/demotion is access-count driven with hysteresis: a freshly
promoted row is demotion-immune for ``min_residency`` windows and at
most ``max_migrations_per_window`` representation changes happen per
window, so adversarial alternating access at the hot-set boundary
cannot thrash.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.causal.engine import CausalEngine, PackedSlab
from repro.causal.policy import CausalPolicy
from repro.causal.results import ClassifyResult
from repro.core import clock as bc
from repro.core import wire
from repro.core.hashing import bloom_indices_host, stable_event_id
from repro.obs.audit import NULL_AUDIT
from repro.obs.observer import resolve

__all__ = ["HybridConfig", "HybridEngine", "HybridSlab", "HybridView"]

#: logical cells minted per chunk of rows (int32), so a bulk admission
#: holds at most 64 MiB of them at once whatever its size
_MINT_CELLS = 1 << 24


@dataclasses.dataclass
class HybridSlab(PackedSlab):
    """A ``PackedSlab`` carrying an exact hot set alongside the tail.

    ``cells_u8``/``base``/``wide`` describe the TAIL rows only; the hot
    rows ride as ``(v, n_private)`` metadata plus their (geometry-
    independent) shadow total sums.  ``local_version`` must be the
    chain prefix length of the query clock this slab will be classified
    against — the exact verdicts are containment tests against it.
    Result rows come back hot-first: ``[0, H)`` hot, ``[H, H+T)`` tail.
    ``HybridEngine.slab`` hands out its read-only hot arrays, shared by
    every slab until the hot set or the tail mirror changes.
    """

    hot_meta: Optional[np.ndarray] = None   # [H, 2] int32 (v, n_private)
    hot_sums: Optional[np.ndarray] = None   # [H, 1] float32 shadow sums
    local_version: int = 0

    @property
    def hot_count(self) -> int:
        return 0 if self.hot_meta is None else int(self.hot_meta.shape[0])

    @property
    def rows(self) -> int:
        return self.hot_count + self.capacity


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Geometry and movement policy of a ``HybridEngine``."""

    m: int = 512                  # tail bloom cells (pow2; fold target)
    k: int = 4                    # hash probes per event
    hot_capacity: int = 64        # exact rows kept on device
    tail_capacity: int = 4096     # packed tail slots
    promote_after: int = 3        # window accesses that earn promotion
    min_residency: int = 2        # windows a hot row is demotion-immune
    max_migrations_per_window: int = 8
    window: int = 256             # touches per migration window
    fp_budget: Optional[float] = None  # attach an AdaptivePolicy when set
    interpret: Optional[bool] = None


@dataclasses.dataclass
class HybridView:
    """One fused classify over the whole population (host-side)."""

    sids: tuple
    hot: np.ndarray               # bool per row: served by the exact path
    q_le_p: np.ndarray
    p_le_q: np.ndarray
    fp_q_before_p: np.ndarray
    fp_p_before_q: np.ndarray
    sum_p: np.ndarray
    sum_q: float
    engine: str = ""

    def _i(self, sid) -> int:
        return self.sids.index(sid)

    def verdict_of(self, sid) -> str:
        i = self._i(sid)
        le, ge = bool(self.q_le_p[i]), bool(self.p_le_q[i])
        if le and ge:
            return "equal"
        if le:
            return "descendant"     # peer is ahead of the query
        if ge:
            return "ancestor"       # peer is in the query's past
        return "concurrent"

    def fp_of(self, sid) -> float:
        """Claimed fp of the strict verdict's direction (0 when none)."""
        i = self._i(sid)
        if bool(self.q_le_p[i]) and not bool(self.p_le_q[i]):
            return float(self.fp_q_before_p[i])
        if bool(self.p_le_q[i]) and not bool(self.q_le_p[i]):
            return float(self.fp_p_before_q[i])
        return 0.0


@dataclasses.dataclass(frozen=True)
class _RowSnapshot:
    """What a sweep derives from the population alone: the hot rows'
    metadata and the view's row order.  Rebuilt when the hot set or the
    tail mirror changes, never changed in place, so slabs and views that
    hold its arrays keep them; the arrays are read-only."""

    hot_meta: np.ndarray          # [H, 2] int32 (v, n_private), a view
                                  # of [2, H] rows
    hot_sums: np.ndarray          # [H, 1] float32 shadow sums
    sids: tuple                   # hot sids, then the tail mirror's order
    hot: np.ndarray               # bool per row: the first H


@dataclasses.dataclass(slots=True)
class _Session:
    """Catalog entry: the exact description every representation of the
    session is derived from."""

    v: int                        # local-chain prefix length
    events: tuple                 # ((hi, lo), ...) private event ids
    access: int = 0
    hot: bool = False
    slot: Optional[int] = None    # tail slot when not hot
    promoted_window: int = -(1 << 30)

    @property
    def n_private(self) -> int:
        return len(self.events)


class HybridEngine:
    """The hybrid front door (see module docstring)."""

    def __init__(self, cfg: HybridConfig = HybridConfig(), *,
                 policy: CausalPolicy | None = None, observer=None,
                 audit=None):
        self.cfg = cfg
        self.m = cfg.m
        self.k = cfg.k
        pol = policy or CausalPolicy(interpret=cfg.interpret)
        if observer is not None and pol.observer is None:
            pol = dataclasses.replace(pol, observer=observer)
        self.engine = CausalEngine(pol)
        self.obs = resolve(observer)
        self.audit = audit if audit is not None else NULL_AUDIT
        # local event chain: probe indices per event (k per row).  Probes
        # are stored mod the CURRENT m and fold exactly on resize.
        self._probes = np.zeros((0, cfg.k), np.int64)
        self._local_cells = np.zeros(cfg.m, np.int64)
        # [V'+1, m] int32 cumulative cells of the chain's first V' events
        # (row v mints a session's prefix), extended lazily
        self._prefix = np.zeros((1, cfg.m), np.int32)
        self.sessions: dict = {}
        # hot set: insertion-ordered sid -> _Session (values alias
        # ``sessions``; the dict itself is the device row order)
        self._hot: dict = {}
        # tail arrays: the §4 packed layout, host-authoritative with a
        # device mirror rebuilt lazily (``_dirty``)
        T = cfg.tail_capacity
        self._t_u8 = np.zeros((T, cfg.m), np.uint8)
        self._t_base = np.zeros(T, np.int64)
        self._t_sums = np.zeros(T, np.float32)
        self._t_alive = np.zeros(T, bool)
        self._t_wide: dict[int, np.ndarray] = {}
        self._t_free: list[int] = list(range(T - 1, -1, -1))
        self._t_order: list = []        # alive sids in slot-scan order
        self._dirty = True
        self._dev = None                # (cells_u8, base, wide, sids)
        # hot metadata and view row order, dropped on any hot-set move
        # and on a mirror rebuild (``_row_snapshot``)
        self._rows: Optional[_RowSnapshot] = None
        # migration window bookkeeping
        self._window_idx = 0
        self._window_touches = 0
        self._window_migrations = 0
        self.promotions = 0
        self.demotions = 0
        self.resizes = 0
        self.mirror_rebuilds = 0
        self.hot_rebuilds = 0
        self.adaptive = None
        if cfg.fp_budget is not None:
            from repro.hybrid.adaptive import AdaptiveConfig, AdaptivePolicy
            self.adaptive = AdaptivePolicy(
                self, AdaptiveConfig(fp_budget=cfg.fp_budget))

    # ------------------------------------------------------------------
    # local chain
    # ------------------------------------------------------------------
    @property
    def local_version(self) -> int:
        return int(self._probes.shape[0])

    def append_local(self, event_hi: int, event_lo: int) -> None:
        """Record one local event: extends the chain every hot verdict
        is a containment test against, and ticks the local clock."""
        self._append_local(np.asarray([[event_hi, event_lo]], np.int64))

    def advance_local(self, count: int = 1) -> None:
        """Append ``count`` fresh deterministic local events."""
        V = self.local_version
        self._append_local(np.asarray(
            [stable_event_id(b"hybrid/local", V + i) for i in range(count)],
            np.int64).reshape(-1, 2))

    def _append_local(self, ids: np.ndarray) -> None:
        probes = self._probe_of(ids[:, 0], ids[:, 1])
        self._probes = np.concatenate([self._probes, probes])
        np.add.at(self._local_cells, probes.ravel(), 1)

    def local_clock(self) -> bc.BloomClock:
        return bc.BloomClock(
            cells=jnp.asarray(_fold_i32(self._local_cells)),
            base=jnp.zeros((), jnp.int32), k=self.k)

    def _probe_of(self, hi, lo) -> np.ndarray:
        """[..., k] int64 probes of event ids at the current geometry."""
        return bloom_indices_host(hi, lo, self.k, self.m).astype(np.int64)

    def _prefix_upto(self, v: int) -> np.ndarray:
        """The prefix-cell table, extended to cover chain prefix ``v``."""
        have = self._prefix.shape[0] - 1
        if v > have:
            new = np.zeros((v - have, self.m), np.int32)
            rows = np.repeat(np.arange(v - have), self.k)
            np.add.at(new, (rows, self._probes[have:v].ravel()), 1)
            np.cumsum(new, axis=0, out=new)
            new += self._prefix[-1]
            self._prefix = np.concatenate([self._prefix, new])
        return self._prefix

    # ------------------------------------------------------------------
    # admission / representation moves
    # ------------------------------------------------------------------
    def admit(self, sid, v: int, events=()) -> None:
        """Register a session from its exact description: a ``v``-long
        prefix of the local chain plus private event ids.  Lands in the
        tail representation; access counters promote it later."""
        ids = np.asarray(list(events), np.int64).reshape(-1, 2)
        self.admit_many([sid], [v], (np.asarray([0, len(ids)]), ids))

    def admit_many(self, sids, v, events=None) -> None:
        """Register many sessions at once, as a loop of ``admit`` would
        (same slots, same rows, same catalog order).

        ``v``: int array of chain prefix lengths, one per sid.
        ``events``: None (no private events) or ``(offsets, ids)``, where
        session ``i``'s private event ids are ``ids[offsets[i]:
        offsets[i+1]]`` and ``ids`` is ``[E, 2]`` ``(hi, lo)``.  A sid
        already present is replaced; a sid may appear once per call."""
        sids = list(sids)
        n = len(sids)
        v = np.asarray(v, np.int64).reshape(-1)
        if v.shape[0] != n:
            raise ValueError(f"{n} sids but {v.shape[0]} versions")
        if events is None:
            offsets, ids = np.zeros(n + 1, np.int64), np.zeros((0, 2))
        else:
            offsets = np.asarray(events[0], np.int64)
            ids = np.asarray(events[1]).reshape(-1, 2)
        if (offsets.shape != (n + 1,) or offsets[0] != 0
                or offsets[-1] != len(ids) or (np.diff(offsets) < 0).any()):
            raise ValueError("events offsets must run 0..len(ids), "
                             "non-decreasing, one more than sids")
        if n and (v.min() < 0 or v.max() > self.local_version):
            raise ValueError(
                f"session prefix v in [{v.min()}, {v.max()}] exceeds local "
                f"chain length {self.local_version}")
        if len(set(sids)) != n:
            raise ValueError("a sid appears twice in one admission")
        with self.obs.trace.span("hybrid.admit_many", rows=n,
                                 private_events=len(ids)):
            for sid in sids:
                if sid in self.sessions:
                    self.release(sid)
            if n > len(self._t_free):
                raise RuntimeError("tail slab full; grow tail_capacity")
            pairs = list(map(tuple, ids.astype(np.int64).tolist()))
            at = offsets.tolist()
            new = [_Session(v=vi, events=tuple(pairs[a:b]))
                   for vi, a, b in zip(v.tolist(), at, at[1:])]
            self.sessions.update(zip(sids, new))
            self._place(new, v, offsets, ids)
            if self.obs:
                self.obs.metrics.counter("hybrid_admitted").inc(n)

    def release(self, sid) -> None:
        s = self.sessions.pop(sid, None)
        if s is None:
            return
        if s.hot:
            self._hot.pop(sid, None)
            self._rows = None
        elif s.slot is not None:
            self._free_slot(s)

    @staticmethod
    def _describe(sessions: list):
        """(v, offsets, ids) of catalog entries, as ``admit_many`` takes."""
        v = np.asarray([s.v for s in sessions], np.int64)
        counts = np.asarray([s.n_private for s in sessions], np.int64)
        offsets = np.concatenate([[0], np.cumsum(counts)])
        ids = np.asarray([e for s in sessions for e in s.events],
                         np.int64).reshape(-1, 2)
        return v, offsets, ids

    def _mint(self, v: np.ndarray, offsets: np.ndarray,
              ids: np.ndarray) -> np.ndarray:
        """[n, m] int32 logical cells at the CURRENT geometry (a fold of
        any previous mint): each row's chain prefix from the prefix-cell
        table plus the probes of its private events."""
        cells = self._prefix_upto(int(v.max(initial=0)))[v]
        if len(ids):
            rows = np.repeat(np.arange(len(v)), np.diff(offsets) * self.k)
            probes = self._probe_of(ids[:, 0], ids[:, 1])
            np.add.at(cells, (rows, probes.ravel()), 1)
        return cells

    def _mint_cells(self, s: _Session) -> np.ndarray:
        """One session's logical cells (the one-row ``_mint``)."""
        return self._mint(*self._describe([s]))[0].astype(np.int64)

    def _place(self, sessions: list, v: np.ndarray, offsets: np.ndarray,
               ids: np.ndarray) -> None:
        """Mint catalog entries into free tail slots, in chunks: the
        slots a loop of single admissions would pop, written the same
        way (u8 residuals over a per-row base, or the exact int32 row on
        the side when the span outgrows a byte)."""
        n = len(sessions)
        if n > len(self._t_free):
            raise RuntimeError("tail slab full; grow tail_capacity")
        slots = np.asarray(self._t_free[len(self._t_free) - n:][::-1],
                           np.int64)
        del self._t_free[len(self._t_free) - n:]
        chunk = max(1, _MINT_CELLS // self.m)
        for a in range(0, n, chunk):
            b = min(a + chunk, n)
            o = offsets[a:b + 1]
            cells = self._mint(v[a:b], o - o[0], ids[o[0]:o[-1]])
            base = cells.min(axis=1)
            resid = cells - base[:, None]
            wide = resid.max(axis=1) > 255
            resid[wide] = 0
            at = slots[a:b]
            self._t_u8[at] = resid.astype(np.uint8)
            self._t_base[at] = np.where(wide, 0, base)
            self._t_sums[at] = cells.sum(axis=1, dtype=np.int64).astype(
                np.float32)
            self._t_alive[at] = True
            for i in np.flatnonzero(wide):
                self._t_wide[int(at[i])] = _fold_i32(cells[i])
        for s, slot in zip(sessions, slots.tolist()):
            s.slot = slot
            s.hot = False
        self._dirty = True

    def _free_slot(self, s: _Session) -> None:
        slot = s.slot
        self._t_alive[slot] = False
        self._t_wide.pop(slot, None)
        self._t_free.append(slot)
        s.slot = None
        self._dirty = True

    def promote(self, sid) -> None:
        """Switch a session to the exact representation."""
        s = self.sessions[sid]
        if s.hot:
            return
        if len(self._hot) >= self.cfg.hot_capacity:
            raise RuntimeError("hot set full; demote first")
        self._free_slot(s)
        s.hot = True
        s.promoted_window = self._window_idx
        self._hot[sid] = s
        self._rows = None
        self.promotions += 1
        self._window_migrations += 1
        if self.obs:
            self.obs.metrics.counter("hybrid_migrations",
                                     kind="promote").inc()

    def demote(self, sid) -> None:
        """Re-mint a hot session back into the packed tail (bit-identical
        to having always been a tail row: minting is deterministic)."""
        s = self.sessions[sid]
        if not s.hot:
            return
        self._hot.pop(sid)
        self._rows = None
        self._place([s], *self._describe([s]))
        self.demotions += 1
        self._window_migrations += 1
        if self.obs:
            self.obs.metrics.counter("hybrid_migrations",
                                     kind="demote").inc()

    # ---- access-driven movement with hysteresis ----
    def touch(self, sid) -> None:
        self._window_touches += 1
        if self._window_touches >= self.cfg.window:
            self._roll_window()
        s = self.sessions[sid]
        s.access += 1
        if s.hot or s.access < self.cfg.promote_after:
            return
        # each promotion is 1 migration; promotion-by-swap costs 2
        budget = (self.cfg.max_migrations_per_window
                  - self._window_migrations)
        if len(self._hot) < self.cfg.hot_capacity:
            if budget >= 1:
                self.promote(sid)
            return
        if budget < 2:
            return
        victim = self._demotion_victim(floor=s.access)
        if victim is not None:
            self.demote(victim)
            self.promote(sid)

    def _demotion_victim(self, floor: int) -> Optional[str]:
        """Least-touched residency-expired hot session strictly colder
        than ``floor``, or None — fresh promotions are immune, so an
        adversarial alternating pattern at the boundary cannot thrash."""
        expired = [
            (s.access, sid) for sid, s in self._hot.items()
            if self._window_idx - s.promoted_window >= self.cfg.min_residency
        ]
        if not expired:
            return None
        access, sid = min(expired)
        return sid if access < floor else None

    def _roll_window(self) -> None:
        self._window_idx += 1
        self._window_touches = 0
        self._window_migrations = 0
        for s in self.sessions.values():
            s.access = 0

    # ------------------------------------------------------------------
    # the fused classify front door
    # ------------------------------------------------------------------
    def _device_tail(self):
        """Alive-compacted device mirror of the tail (lazily rebuilt)."""
        if not self._dirty and self._dev is not None:
            return self._dev
        order = [sid for sid, s in self.sessions.items() if not s.hot]
        slots = np.asarray([self.sessions[sid].slot for sid in order],
                           np.int64)
        if slots.size:
            u8 = self._t_u8[slots]
            base = _fold_i32(self._t_base[slots])
        else:
            u8 = np.zeros((0, self.m), np.uint8)
            base = np.zeros(0, np.int32)
        wide = {}
        for i, sid in enumerate(order):
            slot = self.sessions[sid].slot
            if slot in self._t_wide:
                wide[i] = self._t_wide[slot]
        self._dev = (jnp.asarray(u8), jnp.asarray(base), wide, order)
        self._t_order = order
        self._rows = None
        self._dirty = False
        self.mirror_rebuilds += 1
        if self.obs:
            self.obs.metrics.counter("hybrid_mirror_rebuilds").inc()
        return self._dev

    def _row_snapshot(self) -> _RowSnapshot:
        """The hot metadata and the view's row order, rebuilt only when
        ``promote``, ``demote``, ``release`` of a hot session or a mirror
        rebuild dropped them (call after ``_device_tail``)."""
        if self._rows is not None:
            return self._rows
        # no container per row: at a 65,536-row hot set, per-row lists
        # cost the collector hundreds of passes a sweep over the catalog
        # the metadata is built as [2, H] dense rows, the layout the
        # fused kernel reads, and handed out as its [H, 2] view
        H = len(self._hot)
        rows = np.empty((2, H), np.int32)
        rows[0] = np.fromiter((s.v for s in self._hot.values()), np.int32, H)
        rows[1] = np.fromiter((len(s.events) for s in self._hot.values()),
                              np.int32, H)
        meta = rows.T
        sums = (self.k * meta.sum(axis=1, keepdims=True, dtype=np.int64)
                ).astype(np.float32)
        hot = np.arange(H + len(self._t_order)) < H
        for a in (rows, meta, sums, hot):
            a.setflags(write=False)
        self._rows = _RowSnapshot(hot_meta=meta, hot_sums=sums,
                                  sids=(*self._hot, *self._t_order), hot=hot)
        self.hot_rebuilds += 1
        if self.obs:
            self.obs.metrics.counter("hybrid_hot_rebuilds").inc()
        return self._rows

    def slab(self) -> HybridSlab:
        """The population as one hot-carrying slab (hot rows first)."""
        u8, base, wide, _ = self._device_tail()
        rows = self._row_snapshot()
        return HybridSlab(
            cells_u8=u8, base=base, wide=wide,
            hot_meta=rows.hot_meta, hot_sums=rows.hot_sums,
            local_version=self.local_version)

    def classify(self, *, bn: int | None = None,
                 bm: int | None = None) -> HybridView:
        """Classify the local clock against every session in ONE fused
        device sweep: exact verdicts (fp ≡ 0) for the hot set, packed
        bloom verdicts (bit-identical to a flat slab) for the tail.

        Spans: ``hybrid.classify`` (``hot``, ``tail``, ``m``) holds
        ``hybrid.slab`` (mirror and row-snapshot checks or rebuilds), the
        engine's ``causal.classify``, ``hybrid.view`` (one readback and
        the view) and ``hybrid.observe`` (metrics, adaptive policy)."""
        trace = self.obs.trace
        with trace.span("hybrid.classify", m=self.m) as root:
            with trace.span("hybrid.slab"):
                slab = self.slab()
                rows = self._row_snapshot()
            H, T = slab.hot_count, slab.capacity
            root.set(hot=H, tail=T)
            query = self.local_clock()
            if H and T:
                res = self.engine.classify(query, slab, bn=bn, bm=bm)
            elif T:
                res = self.engine.classify(
                    query, PackedSlab(slab.cells_u8, slab.base,
                                      wide=slab.wide), bn=bn, bm=bm)
            elif H:
                res = self._hot_only_result(slab)
            else:
                return HybridView(sids=(), hot=np.zeros(0, bool),
                                  q_le_p=np.zeros(0, bool),
                                  p_le_q=np.zeros(0, bool),
                                  fp_q_before_p=np.zeros(0, np.float32),
                                  fp_p_before_q=np.zeros(0, np.float32),
                                  sum_p=np.zeros(0, np.float32),
                                  sum_q=float(self._local_cells.sum()),
                                  engine="empty")
            with trace.span("hybrid.view"):
                got = jax.device_get((res.q_le_p, res.p_le_q,
                                      res.fp_q_before_p, res.fp_p_before_q,
                                      res.sum_p, res.sum_q))
                q_le_p, p_le_q, fp_qp, fp_pq, sum_p, sum_q = got
                view = HybridView(
                    sids=rows.sids, hot=rows.hot,
                    q_le_p=np.asarray(q_le_p, bool),
                    p_le_q=np.asarray(p_le_q, bool),
                    fp_q_before_p=np.asarray(fp_qp, np.float32),
                    fp_p_before_q=np.asarray(fp_pq, np.float32),
                    sum_p=np.asarray(sum_p, np.float32),
                    sum_q=float(sum_q),
                    engine=res.engine or "")
                if self.obs:
                    self.obs.metrics.counter("hybrid_readback_bytes").inc(
                        sum(np.asarray(x).nbytes for x in got))
            with trace.span("hybrid.observe"):
                self._observe(view, H, T)
        return view

    def _observe(self, view: HybridView, H: int, T: int) -> None:
        """The observer's metrics and the adaptive policy's window."""
        if self.obs:
            self.obs.metrics.counter("hybrid_classified", path="hot").inc(H)
            self.obs.metrics.counter("hybrid_classified", path="tail").inc(T)
            self.obs.metrics.gauge("hybrid_hot_occupancy").set(H)
            self.obs.metrics.gauge("hybrid_tail_m").set(self.m)
            strict = view.q_le_p[H:] ^ view.p_le_q[H:]
            fps = np.where(view.q_le_p[H:], view.fp_q_before_p[H:],
                           view.fp_p_before_q[H:])[strict]
            if fps.size:
                self.obs.metrics.histogram("hybrid_tail_fp").observe_many(
                    np.clip(fps, 1e-30, 1.0))
        if self.adaptive is not None:
            self.adaptive.observe(view)

    def _hot_only_result(self, slab: HybridSlab) -> ClassifyResult:
        """Host containment math for the degenerate no-tail population —
        same verdict semantics as the kernel's hot lanes."""
        V = slab.local_version
        v = slab.hot_meta[:, 0]
        npriv = slab.hot_meta[:, 1]
        z = np.zeros(v.shape[0], np.float32)
        return ClassifyResult(
            q_le_p=jnp.asarray(V <= v), p_le_q=jnp.asarray((v <= V)
                                                           & (npriv == 0)),
            sum_q=jnp.asarray(np.float32(self._local_cells.sum())),
            sum_p=jnp.asarray(slab.hot_sums[:, 0]),
            fp_q_before_p=jnp.asarray(z), fp_p_before_q=jnp.asarray(z),
            engine="hot_exact")

    def hot_hit_rate(self) -> float:
        """Fraction of classified rows served by the exact path."""
        if not self.obs:
            return 0.0
        hot = self.obs.metrics.counter("hybrid_classified", path="hot").value
        tail = self.obs.metrics.counter("hybrid_classified",
                                        path="tail").value
        total = hot + tail
        return hot / total if total else 0.0

    # ------------------------------------------------------------------
    # all-pairs
    # ------------------------------------------------------------------
    def pairs(self, *, bi=None, bj=None, bm=None):
        """All-pairs over the population: the packed sweep over every
        row's bloom shadow (bit-identical to a flat slab), with the
        hot-hot block patched to exact containment verdicts (fp ≡ 0)."""
        hot_sids = list(self._hot)
        _, _, _, tail_sids = self._device_tail()
        order = hot_sids + tail_sids
        N = len(order)
        if N == 0:
            raise ValueError("empty population")
        u8 = np.zeros((N, self.m), np.uint8)
        base = np.zeros(N, np.int64)
        wide: dict[int, np.ndarray] = {}
        for i, sid in enumerate(order):
            s = self.sessions[sid]
            cells = (self._mint_cells(s) if s.hot
                     else self._tail_logical(s.slot))
            b = int(cells.min()) if cells.size else 0
            resid = cells - b
            if resid.max(initial=0) <= 255:
                u8[i] = resid.astype(np.uint8)
                base[i] = b
            else:
                wide[i] = _fold_i32(cells)
        slab = PackedSlab(jnp.asarray(u8), jnp.asarray(_fold_i32(base)),
                          base_host=base, wide=wide)
        res = self.engine.pairs(slab, bi=bi, bj=bj, bm=bm)
        H = len(hot_sids)
        if H:
            le = np.array(res.le, bool)
            ge = np.array(res.ge, bool)
            fp = np.array(res.fp, np.float32)
            hs = [self._hot[sid] for sid in hot_sids]
            ev = [set(s.events) for s in hs]
            for a in range(H):
                for b_ in range(H):
                    le[a, b_] = (hs[a].v <= hs[b_].v
                                 and ev[a] <= ev[b_])
                    fp[a, b_] = 0.0
            ge[:H, :H] = le[:H, :H].T
            conc = np.array(res.conc, bool)
            conc[:H, :H] = ~(le[:H, :H] | ge[:H, :H])
            res = dataclasses.replace(
                res, le=jnp.asarray(le), ge=jnp.asarray(ge),
                conc=jnp.asarray(conc), fp=jnp.asarray(fp),
                engine=(res.engine or "") + "+hot_exact")
        return res, order

    def _tail_logical(self, slot: int) -> np.ndarray:
        if slot in self._t_wide:
            return (np.asarray(self._t_wide[slot], np.int64)
                    & 0xFFFFFFFF)
        return self._t_u8[slot].astype(np.int64) + int(self._t_base[slot])

    # ------------------------------------------------------------------
    # geometry resize (quiesce-point fold)
    # ------------------------------------------------------------------
    def resize_tail(self, new_m: int, *, detail: str = "") -> None:
        """Fold the tail geometry to ``new_m`` (a power-of-two divisor
        of the current ``m``) at a quiesce point.

        The fold is EXACT: probe indices are ``mod m``, so
        ``cell'[j] = Σ_i cells[j + i·new_m]`` equals minting at
        ``new_m`` outright, and total sums are geometry-independent.
        Every live row gets an audit record carrying its pre-fold wire
        frame and the folded row's CRC, so ``replay_resize`` re-checks
        the whole migration bit-for-bit."""
        from repro.hybrid.adaptive import fold_pow2
        old_m = self.m
        if new_m == old_m:
            return
        if new_m <= 0 or old_m % new_m or (new_m & (new_m - 1)):
            raise ValueError(f"new_m={new_m} must be a pow2 divisor "
                             f"of m={old_m}")
        live = [(sid, s) for sid, s in self.sessions.items() if not s.hot]
        self.audit.record(
            "resize", "hybrid/tail",
            detail=json.dumps({"old_m": old_m, "new_m": new_m,
                               "rows": len(live),
                               "policy": detail}, sort_keys=True))
        for sid, s in live:
            cells = self._tail_logical(s.slot)
            snap = {"cells": _fold_i32(cells), "base": 0, "k": self.k}
            folded = fold_pow2(cells, new_m)
            self.audit.record(
                "resize_row", sid,
                local_frame=wire.encode_clock(snap),
                peer_crc=wire.cells_crc(_fold_i32(folded)),
                detail=json.dumps({"new_m": new_m}))
        # fold the chain probes + local clock, then re-slot every row
        self.m = new_m
        self._probes = self._probes % new_m
        self._prefix = np.zeros((1, new_m), np.int32)
        self._local_cells = fold_pow2(self._local_cells, new_m)
        self._t_u8 = np.zeros((self.cfg.tail_capacity, new_m), np.uint8)
        self._t_base[:] = 0
        self._t_sums[:] = 0.0
        self._t_alive[:] = False
        self._t_wide.clear()
        self._t_free = list(range(self.cfg.tail_capacity - 1, -1, -1))
        sessions = [s for _, s in live]
        self._place(sessions, *self._describe(sessions))
        self.resizes += 1
        self._dirty = True
        if self.obs:
            self.obs.metrics.counter("hybrid_resizes").inc()
            self.obs.metrics.gauge("hybrid_tail_m").set(new_m)


def _fold_i32(cells) -> np.ndarray:
    """Fold int64 logical values onto the int32 mod-2^32 circle."""
    return (np.asarray(cells, np.int64)
            & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
