"""Integer hashing for bloom-clock event ids.

The paper treats hash functions as a black box producing k independent
indices per event.  We follow standard bloom-filter engineering practice:

- events are uint64 identifiers (callers hash arbitrary payloads down to
  64 bits however they like; `stable_event_id` is provided for tuples of
  ints / bytes),
- two independent 64-bit finalizers (splitmix64 and a murmur3-style
  variant) produce h1, h2,
- the k indices come from double hashing (Kirsch-Mitzenmacher 2006):
  idx_i = (h1 + i * h2) mod m, which is provably as good as k independent
  hashes for bloom filters.

Everything is pure jnp on uint32 pairs so it runs identically on
TPU (which has no native 64-bit multiply in the VPU fast path) and CPU.
We represent a 64-bit value as (hi, lo) uint32 lanes.

``bloom_indices_host`` is the same double hash in vectorised numpy
uint64 arithmetic, bit for bit: host code that hashes many events at
once (bulk admission, a local chain) uses it instead of paying the
eager jnp ops per event.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

__all__ = [
    "splitmix64",
    "murmur64",
    "bloom_indices",
    "bloom_indices_host",
    "stable_event_id",
]

_MASK32 = np.uint32(0xFFFFFFFF)


def _mul64(a_hi, a_lo, b_hi, b_lo):
    """64x64 -> low 64 bits of product, on uint32 lanes."""
    a_lo = a_lo.astype(jnp.uint32)
    b_lo = b_lo.astype(jnp.uint32)
    # 32x32 -> 64 via 16-bit split to stay in uint32 arithmetic.
    a0 = a_lo & 0xFFFF
    a1 = a_lo >> 16
    b0 = b_lo & 0xFFFF
    b1 = b_lo >> 16
    ll = a0 * b0
    lh = a0 * b1
    hl = a1 * b0
    hh = a1 * b1
    mid = (ll >> 16) + (lh & 0xFFFF) + (hl & 0xFFFF)
    lo = (ll & 0xFFFF) | ((mid & 0xFFFF) << 16)
    carry = mid >> 16
    hi_from_lo = hh + (lh >> 16) + (hl >> 16) + carry
    hi = (a_hi * b_lo + a_lo * b_hi + hi_from_lo).astype(jnp.uint32)
    return hi, lo


def _add64(a_hi, a_lo, b_hi, b_lo):
    lo = (a_lo + b_lo).astype(jnp.uint32)
    carry = (lo < a_lo).astype(jnp.uint32)
    hi = (a_hi + b_hi + carry).astype(jnp.uint32)
    return hi, lo


def _xor64(a_hi, a_lo, b_hi, b_lo):
    return a_hi ^ b_hi, a_lo ^ b_lo


def _shr64(hi, lo, n: int):
    if n == 0:
        return hi, lo
    if n >= 32:
        return jnp.zeros_like(hi), (hi >> (n - 32)).astype(jnp.uint32)
    lo2 = ((lo >> n) | (hi << (32 - n))).astype(jnp.uint32)
    hi2 = (hi >> n).astype(jnp.uint32)
    return hi2, lo2


def _const64(v: int):
    return np.uint32((v >> 32) & 0xFFFFFFFF), np.uint32(v & 0xFFFFFFFF)


def splitmix64(hi, lo):
    """splitmix64 finalizer on (hi, lo) uint32 lanes."""
    c1 = _const64(0x9E3779B97F4A7C15)
    c2 = _const64(0xBF58476D1CE4E5B9)
    c3 = _const64(0x94D049BB133111EB)
    hi, lo = _add64(hi, lo, *c1)
    x = _xor64(hi, lo, *_shr64(hi, lo, 30))
    hi, lo = _mul64(*x, *c2)
    x = _xor64(hi, lo, *_shr64(hi, lo, 27))
    hi, lo = _mul64(*x, *c3)
    hi, lo = _xor64(hi, lo, *_shr64(hi, lo, 31))
    return hi, lo


def murmur64(hi, lo):
    """murmur3 fmix64 finalizer on (hi, lo) uint32 lanes."""
    c1 = _const64(0xFF51AFD7ED558CCD)
    c2 = _const64(0xC4CEB9FE1A85EC53)
    hi, lo = _xor64(hi, lo, *_shr64(hi, lo, 33))
    hi, lo = _mul64(hi, lo, *c1)
    hi, lo = _xor64(hi, lo, *_shr64(hi, lo, 33))
    hi, lo = _mul64(hi, lo, *c2)
    hi, lo = _xor64(hi, lo, *_shr64(hi, lo, 33))
    return hi, lo


def bloom_indices(event_hi, event_lo, k: int, m: int):
    """k bloom-filter indices in [0, m) for each event.

    event_hi/event_lo: uint32 arrays of identical shape S (64-bit event ids
    split into lanes).  Returns uint32 array of shape S + (k,).

    Double hashing: idx_i = (h1 + i*h2) mod m computed in 32-bit space.
    m is assumed << 2^32; we fold the 64-bit hashes to 32 bits first
    (xor-fold) which preserves uniformity.
    """
    event_hi = jnp.asarray(event_hi, jnp.uint32)
    event_lo = jnp.asarray(event_lo, jnp.uint32)
    h1_hi, h1_lo = splitmix64(event_hi, event_lo)
    h2_hi, h2_lo = murmur64(event_hi, event_lo)
    h1 = (h1_hi ^ h1_lo).astype(jnp.uint32)
    h2 = (h2_hi ^ h2_lo).astype(jnp.uint32)
    # force h2 odd so the stride is coprime with any power-of-two m and
    # never collapses the k probes onto one index
    h2 = h2 | jnp.uint32(1)
    i = jnp.arange(k, dtype=jnp.uint32)
    idx = h1[..., None] + i * h2[..., None]
    return (idx % jnp.uint32(m)).astype(jnp.uint32)


def _splitmix64_host(x: np.ndarray) -> np.ndarray:
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _murmur64_host(x: np.ndarray) -> np.ndarray:
    x = (x ^ (x >> np.uint64(33))) * np.uint64(0xFF51AFD7ED558CCD)
    x = (x ^ (x >> np.uint64(33))) * np.uint64(0xC4CEB9FE1A85EC53)
    return x ^ (x >> np.uint64(33))


def _fold32(x: np.ndarray) -> np.ndarray:
    return ((x >> np.uint64(32)) ^ (x & np.uint64(0xFFFFFFFF))).astype(
        np.uint32)


def bloom_indices_host(event_hi, event_lo, k: int, m: int) -> np.ndarray:
    """``bloom_indices`` on the host: numpy uint64 arithmetic wraps mod
    2^64 exactly as the (hi, lo) lanes do, so the indices are equal bit
    for bit.  Returns a uint32 array of shape S + (k,)."""
    hi = np.asarray(event_hi, np.uint32)
    lo = np.asarray(event_lo, np.uint32)
    x = np.atleast_1d((hi.astype(np.uint64) << np.uint64(32))
                      | lo.astype(np.uint64))
    h1 = _fold32(_splitmix64_host(x)).astype(np.uint64)
    h2 = (_fold32(_murmur64_host(x)) | np.uint32(1)).astype(np.uint64)
    i = np.arange(k, dtype=np.uint64)
    idx = (h1[..., None] + i * h2[..., None]) & np.uint64(0xFFFFFFFF)
    return (idx % np.uint64(m)).astype(np.uint32).reshape(hi.shape + (k,))


def stable_event_id(*parts) -> tuple[int, int]:
    """Deterministically mix python ints / bytes into a 64-bit event id.

    Returns (hi, lo) uint32 python ints.  Host-side helper (not traced).
    """
    acc = 0xCBF29CE484222325  # FNV offset basis
    for p in parts:
        if isinstance(p, bytes):
            data = p
        elif isinstance(p, str):
            data = p.encode()
        else:
            data = int(p).to_bytes(8, "little", signed=False)
        for b in data:
            acc ^= b
            acc = (acc * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF  # FNV prime
    return (acc >> 32) & 0xFFFFFFFF, acc & 0xFFFFFFFF
