"""Shared by the query families: Zipf draws and weighted expansion."""

from __future__ import annotations

import numpy as np


def zipf_cdf(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** s
    return np.cumsum(p / p.sum())


def distinct_ranks(rng: np.random.Generator, cdf: np.ndarray, k: int
                   ) -> list[int]:
    """k different ranks drawn from the distribution (redrawing
    repeats)."""
    out: list[int] = []
    while len(out) < k:
        r = int(np.searchsorted(cdf, rng.random(), side="right"))
        r = min(r, len(cdf) - 1)
        if r not in out:
            out.append(r)
    return out


def spread(weights: list[float], n: int) -> list[int]:
    """n items over the weights by largest remainder: the same counts
    for every seed, each kind as near its share as whole numbers go."""
    w = np.array(weights, dtype=np.float64)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()
