"""Open-loop request schedules: Poisson arrivals, Zipf-skewed task ids.

Independent users make an open loop: request i is due at ``due[i]``
whatever happened to earlier ones.  Arrivals are a Poisson process of
``rate`` per second over ``[0, seconds)``; each request names a task drawn
from a Zipf(``zipf_a``) law over ``m`` tasks (rank k has weight k^-a, the
ranks laid on task ids by a seeded permutation) and a feature row drawn
uniformly from a pool of ``pool_rows`` rows.  Everything comes from one
NumPy generator seeded with the run's seed.
"""
from __future__ import annotations

import numpy as np


def zipf_weights(m: int, a: float) -> np.ndarray:
    w = np.arange(1, m + 1, dtype=np.float64) ** (-a)
    return w / w.sum()


def open_loop(rng: np.random.Generator, *, rate: float, seconds: float,
              m: int, zipf_a: float, pool_rows: int):
    """(due (N,) float64 seconds, task ids (N,) int32, pool rows (N,) int64)."""
    if rate <= 0 or seconds <= 0:
        raise ValueError(f"rate {rate} and seconds {seconds} must be > 0")
    # draw a few sigma more gaps than the mean needs, keep those in range
    mean = rate * seconds
    gaps = rng.exponential(1.0 / rate, size=int(mean + 6 * mean ** 0.5 + 16))
    due = np.cumsum(gaps)
    due = due[due < seconds]
    n = due.shape[0]
    perm = rng.permutation(m)
    ranks = rng.choice(m, size=n, p=zipf_weights(m, zipf_a))
    ids = perm[ranks].astype(np.int32)
    rows = rng.integers(0, pool_rows, size=n)
    return due, ids, rows
