"""Seeded causal histories and the clocks minted from them.

A replica integrates the events of ``writers`` independent writer
chains.  Its state is a version vector ``V`` (events seen per writer);
its bloom clock is the sum over writers of the cumulative one-hot cells
of each chain's first ``V[w]`` events.  A session (or peer) is minted
from a version vector ``v`` plus ``P`` private events the replica never
sees.  The vector truth is then exact:

- ``P == 0`` and ``v <= V``: the session is in the replica's past
  (related); bloom dominance never misses it (paper §3).
- ``P > 0``: the session is concurrent with every replica state; bloom
  may still call it related (a false positive, priced by Eq. 3).

Event cells come from the seed alone, with k independent cells per
event, so nothing here depends on the program's hashing.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator per (seed, stream); any whole number works."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


@dataclasses.dataclass
class History:
    m: int
    k: int
    writers: int
    cum: np.ndarray          # [W, E + 1, m] int32 cumulative cells

    @classmethod
    def make(cls, m: int, k: int, writers: int, events: int,
             seed: int) -> "History":
        rng = seed_rng(seed, 1)
        cells = rng.integers(0, m, (writers, events, k))
        inc = np.zeros((writers, events + 1, m), np.int32)
        w_idx = np.repeat(np.arange(writers), events * k)
        e_idx = np.tile(np.repeat(np.arange(1, events + 1), k), writers)
        np.add.at(inc, (w_idx, e_idx, cells.reshape(-1)), 1)
        for e in range(1, events + 1):      # row by row: np.cumsum along
            inc[:, e] += inc[:, e - 1]      # axis 1 is ten times slower
        return cls(m=m, k=k, writers=writers, cum=inc)

    @property
    def events(self) -> int:
        return self.cum.shape[1] - 1

    def cells(self, v: np.ndarray, priv: np.ndarray | None = None
              ) -> np.ndarray:
        """[n, m] int32 logical cells of version vectors ``v`` [n, W]
        plus private cells ``priv`` [n, P*k] (-1 = none), on the host."""
        v = np.asarray(v)
        out = np.zeros((v.shape[0], self.m), np.int32)
        for w in range(self.writers):
            out += self.cum[w, v[:, w]]
        if priv is not None:
            rows, cols = np.nonzero(priv >= 0)
            np.add.at(out, (rows, priv[rows, cols]), 1)
        return out


def replica_versions(writers: int, start: int, ticks: int,
                     events_per_tick: int) -> np.ndarray:
    """[ticks + 1, W] version vectors: tick t adds one event to each of
    ``events_per_tick`` writers, round robin."""
    out = np.full((ticks + 1, writers), start, np.int64)
    for t in range(1, ticks + 1):
        out[t] = out[t - 1]
        for j in range(events_per_tick):
            out[t, ((t - 1) * events_per_tick + j) % writers] += 1
    return out


def private_cells(rng: np.random.Generator, n: int, events: int, m: int,
                  k: int, on: np.ndarray) -> np.ndarray:
    """[n, events*k] private cell indices, -1 where ``on`` is False."""
    idx = rng.integers(0, m, (n, events * k)).astype(np.int32)
    return np.where(np.asarray(on, bool)[:, None], idx, -1)


@functools.lru_cache(maxsize=None)
def _mint_fn(writers: int):
    import jax
    import jax.numpy as jnp

    def mint(cum, v, priv):
        cells = cum[0][v[:, 0]]
        for w in range(1, writers):
            cells = cells + cum[w][v[:, w]]
        rows = jnp.broadcast_to(jnp.arange(v.shape[0])[:, None], priv.shape)
        return cells.at[rows, jnp.maximum(priv, 0)].add(
            (priv >= 0).astype(jnp.int32))

    return jax.jit(mint)


def mint_on_device(cum_dev, v: np.ndarray, priv: np.ndarray):
    """The same cells as ``History.cells``, made on the device in one
    jitted call (set-up makes the population this way)."""
    import jax.numpy as jnp
    return _mint_fn(cum_dev.shape[0])(cum_dev, jnp.asarray(v, jnp.int32),
                                      jnp.asarray(priv, jnp.int32))
