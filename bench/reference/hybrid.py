"""The hybrid session store's verdicts, written out plainly.

A session is a catalog entry: a prefix length ``v`` of the replica's
local event chain plus private event ids the replica never saw.  The
replica's own clock is the chain at version ``V``.  The exact order is
containment:

- ``q ≼ p`` (the replica is in the session's past) iff ``V ≤ v``;
- ``p ≼ q`` (the session is in the replica's past) iff ``v ≤ V`` and the
  session has no private events.

Hot rows are served from the catalog, so these are their verdicts, with
fp exactly 0 and the sum ``k·(v + n_private)``.  Tail rows are bloom
shadows: the logical cells of the chain's first ``v`` events plus the
session's private events, each event hashed to ``k`` cells by the
double hash below (this module's own numpy port: splitmix64 gives h1,
murmur3's fmix64 gives h2, both folded to 32 bits by xor, h2 forced
odd, cell ``i`` at ``(h1 + i·h2) mod 2^32 mod m``).  Their verdicts,
sums and fp come from ``reference.bloom`` over those cells.

The i-th local event's id is FNV-1a-64 of ``b"hybrid/local"`` followed
by ``i`` as 8 little-endian bytes, split into (hi, lo) 32-bit halves.
"""
from __future__ import annotations

import numpy as np

LOCAL_TAG = b"hybrid/local"
_U64 = 0xFFFFFFFFFFFFFFFF


def fnv1a64(data: bytes) -> int:
    acc = 0xCBF29CE484222325
    for b in data:
        acc = ((acc ^ b) * 0x100000001B3) & _U64
    return acc


def local_event_ids(count: int) -> np.ndarray:
    """[count, 2] (hi, lo) ids of the local chain's first events."""
    ids = [fnv1a64(LOCAL_TAG + i.to_bytes(8, "little"))
           for i in range(count)]
    return np.asarray([[x >> 32, x & 0xFFFFFFFF] for x in ids],
                      np.int64).reshape(-1, 2)


def _mix(x, shifts, mults):
    for s, c in zip(shifts, mults):
        x = (x ^ (x >> np.uint64(s))) * np.uint64(c)
    return x


def cells_of(ids: np.ndarray, k: int, m: int) -> np.ndarray:
    """[E, k] cell indices of events ``ids`` [E, 2] (hi, lo)."""
    ids = np.asarray(ids, np.uint64).reshape(-1, 2)
    x = (ids[:, 0] << np.uint64(32)) | ids[:, 1]
    s = _mix(x + np.uint64(0x9E3779B97F4A7C15), (30, 27),
             (0xBF58476D1CE4E5B9, 0x94D049BB133111EB))
    s ^= s >> np.uint64(31)
    u = _mix(x, (33, 33), (0xFF51AFD7ED558CCD, 0xC4CEB9FE1A85EC53))
    u ^= u >> np.uint64(33)
    low = np.uint64(0xFFFFFFFF)
    h1 = (s >> np.uint64(32)) ^ (s & low)
    h2 = ((u >> np.uint64(32)) ^ (u & low)) | np.uint64(1)
    i = np.arange(k, dtype=np.uint64)
    idx = (h1[:, None] + i * h2[:, None]) & low
    return (idx % np.uint64(m)).astype(np.int64)


def prefix_cells(versions: int, k: int, m: int) -> np.ndarray:
    """[versions + 1, m] int32: row v holds the cells of the local
    chain's first v events."""
    cells = cells_of(local_event_ids(versions), k, m)
    out = np.zeros((versions + 1, m), np.int32)
    for e in range(versions):
        out[e + 1] = out[e]
        np.add.at(out[e + 1], cells[e], 1)
    return out


def private_cells(offsets: np.ndarray, ids: np.ndarray, k: int, m: int,
                  width: int) -> np.ndarray:
    """[n, width] cell indices of each session's private events, -1
    where a session has fewer (``width`` = most events a session has
    times k)."""
    offsets = np.asarray(offsets, np.int64)
    n = len(offsets) - 1
    counts = np.diff(offsets) * k
    out = np.full((n, width), -1, np.int32)
    rows = np.repeat(np.arange(n), counts)
    cols = np.arange(counts.sum()) - np.repeat(offsets[:-1] * k, counts)
    out[rows, cols] = cells_of(ids, k, m).reshape(-1)
    return out


def exact(v: np.ndarray, n_private: np.ndarray, V: int):
    """(p ≼ q, q ≼ p) of sessions against the chain at version ``V``."""
    v = np.asarray(v)
    return (v <= V) & (np.asarray(n_private) == 0), V <= v

