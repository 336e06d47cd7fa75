"""The one load generator: arrivals, request pools and samples.

Every traffic mix is a data file under ``bench/traffic/``; this module turns
its parameters and ``--seed`` into the same work for every seed, in another
order: the gaps between arrivals are the quantiles of the mix's
distribution, shuffled by the seed, and inputs are drawn from the seed.
"""

from __future__ import annotations

import math

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any seed below 2**63."""
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), stream])


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of every request."""
    kind = traffic.get("arrivals", "poisson")
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate   # Exp(rate) quantiles
    gaps = rng_for(seed, 1).permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())


def normal_pool(shape, count: int, seed: int, dtype) -> list[np.ndarray]:
    """``count`` standard-normal host arrays of ``shape`` in ``dtype``."""
    rng = rng_for(seed, 2)
    return [rng.standard_normal(shape, dtype=np.float32).astype(dtype)
            for _ in range(count)]


def sample_ids(n: int, k: int, seed: int) -> list[int]:
    """A seeded sample of ``k`` of ``n`` request ids, always with the last."""
    k = min(k, n)
    ids = set(rng_for(seed, 4).choice(n, size=k, replace=False).tolist())
    ids.discard(n - 1)
    return sorted(list(ids)[:k - 1]) + [n - 1] if k else []


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by linear interpolation between ranks."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
