"""Seeded request parameters: a Zipf key sampler (YCSB's
ZipfianGenerator law: rank r drawn with probability proportional to
1 / r**theta) and per-client streams."""

from __future__ import annotations

import numpy as np


def stream(seed: int, *path: int) -> np.random.Generator:
    """An independent generator for one purpose of one run."""
    return np.random.default_rng(np.random.SeedSequence([seed, *path]))


class Zipf:
    """Ranks 0..n-1 with P(r) proportional to 1 / (r + 1)**theta."""

    def __init__(self, n: int, theta: float):
        w = np.arange(1, n + 1, dtype=np.float64) ** -theta
        self.cdf = np.cumsum(w)
        self.cdf /= self.cdf[-1]

    def ranks(self, rng: np.random.Generator, size: int) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(size), side="right")
        return np.minimum(r, len(self.cdf) - 1)

