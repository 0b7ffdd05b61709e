"""The one traffic generator: turns a mix file into a seeded schedule.

A mix file (``bench/traffic/<name>.json``) holds parameters only:

- ``loop``: ``"open"`` (requests sent when due, whatever the system
  does) or ``"closed"`` (one caller, each call after the last returns);
- open loop: ``rate_per_s``, ``update_fraction`` and
  ``key_distribution`` (``"zipfian"`` with ``zipf_theta``, or
  ``"uniform"``); every request due in the window is sent, however late;
- closed loop: ``ticks_between_calls`` (replica ticks between calls).

Every seed gives the same number of requests of each kind; the seed
draws their arrival times, their order and their keys.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib.history import seed_rng

LOOPS = ("open", "closed")


@dataclasses.dataclass
class Schedule:
    """Open-loop requests, by due time (seconds from the window start)."""

    due: np.ndarray          # [n] float64, sorted
    update: np.ndarray       # [n] bool: update (admit) or query
    key: np.ndarray          # [n] int64 item index

    def __len__(self) -> int:
        return len(self.due)


def check_mix(traffic: dict) -> None:
    if traffic.get("loop") not in LOOPS:
        raise ValueError(f"traffic loop must be one of {LOOPS}")
    if traffic["loop"] == "open":
        for key in ("rate_per_s", "update_fraction", "key_distribution"):
            if key not in traffic:
                raise ValueError(f"open-loop traffic needs {key!r}")


def zipf_cdf(n_items: int, theta: float) -> np.ndarray:
    """CDF of YCSB's zipfian ranks: P(rank r) proportional to r^-theta."""
    w = np.arange(1, n_items + 1, dtype=np.float64) ** -theta
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def draw_keys(rng: np.random.Generator, n: int, n_items: int,
              traffic: dict) -> np.ndarray:
    dist = traffic["key_distribution"]
    if dist == "uniform":
        return rng.integers(0, n_items, n)
    if dist != "zipfian":
        raise ValueError(f"unknown key_distribution {dist!r}")
    ranks = np.searchsorted(zipf_cdf(n_items, traffic["zipf_theta"]),
                            rng.random(n), side="right")
    ranks = np.minimum(ranks, n_items - 1)
    # scramble: popular ranks land on items spread over the key space
    return rng.permutation(n_items)[ranks]


def open_loop(traffic: dict, seconds: float, seed: int, n_items: int,
              stream: int = 10, rate: float | None = None) -> Schedule:
    """Poisson arrivals at the mix's rate (given their count, arrival
    times are sorted uniforms over the window)."""
    check_mix(traffic)
    rate = traffic["rate_per_s"] if rate is None else rate
    n = int(round(rate * seconds))
    rng = seed_rng(seed, stream)
    due = np.sort(rng.random(n)) * seconds
    update = np.zeros(n, bool)
    update[:int(round(n * traffic["update_fraction"]))] = True
    rng.shuffle(update)
    return Schedule(due=due, update=update,
                    key=draw_keys(rng, n, n_items, traffic))
