"""The one traffic generator: it reads a mix's parameters from
``chipbench/traffic/<mix>.json`` and turns them, with the seed, into job
sizes, job tokens and arrival times.

Every seed gets the same work in another order. Job sizes come in decks:
a deck holds the ``deck`` quantiles of the size distribution (lognormal
with the mix's median and sigma, clipped to [min, max]), and each deck is
dealt in a fresh seeded order. An open loop's arrivals are the quantiles
of the exponential distribution, shuffled, laid end to end in operational
time and mapped through the integrated rate, which repeats a burst of
``factor`` times the base rate for ``share`` of every ``period_s``
(BurstGPT-style modulation); the burst's phase comes from the seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def deck_sizes(lengths: dict) -> np.ndarray:
    """Sorted job sizes of one deck."""
    n = int(lengths["deck"])
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    sizes = np.exp(math.log(lengths["median"])
                   + lengths["sigma"] * np.asarray(z))
    return np.clip(np.rint(sizes), lengths["min"], lengths["max"]).astype(
        np.int64)


def size_stream(lengths: dict, rng: np.random.Generator):
    """Endless job sizes: one seeded permutation of the deck after
    another."""
    deck = deck_sizes(lengths)
    while True:
        yield from deck[rng.permutation(len(deck))]


def job_tokens(pool: np.ndarray, n: int, rng: np.random.Generator):
    """A job of ``n`` tokens: ceil(n / doc) pool documents drawn by the
    seed, laid end to end and cut to ``n``, so each chunk of the job's
    chunking (chunk size == document length) is one whole document."""
    n_docs, doc = pool.shape
    pick = rng.integers(0, n_docs, -(-n // doc))
    return pool[pick].reshape(-1)[:n].astype(np.int32)


def replay_order(n_replay: int, n_jobs: int,
                 rng: np.random.Generator) -> np.ndarray:
    """Which stored container each of ``n_jobs`` read-backs asks for:
    seeded permutations of the replay set laid end to end, so every
    container is read as often as the others, give or take one."""
    decks = -(-n_jobs // n_replay)
    return np.concatenate([rng.permutation(n_replay)
                           for _ in range(decks)])[:n_jobs]


def _rate_factor(t, burst: dict, phase: float):
    """Rate relative to the base rate at times ``t``."""
    period = burst["period_s"]
    inside = ((np.asarray(t) / period + phase) % 1.0) < burst["share"]
    return np.where(inside, burst["factor"], 1.0)


def mean_factor(burst: dict) -> float:
    return 1.0 + (burst["factor"] - 1.0) * burst["share"]


def arrival_times(mix: dict, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Due times (s from the window's start) of an open loop's jobs:
    ``rate_jobs_per_s * seconds`` arrivals, the last due at ``seconds``."""
    burst = mix["burst"]
    n = max(1, int(round(mix["rate_jobs_per_s"] * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)        # exponential quantiles
    tau = np.cumsum(gaps[rng.permutation(n)])
    phase = float(rng.random())
    grid = np.linspace(0.0, seconds, 200001)
    lam = np.cumsum(_rate_factor(grid, burst, phase)) * (grid[1] - grid[0])
    lam *= tau[-1] / lam[-1]                  # the last arrival at `seconds`
    return np.interp(tau, lam, grid)
