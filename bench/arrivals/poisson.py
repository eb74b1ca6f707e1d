"""Open-loop Poisson arrivals at the cell's rate (``rate_rps``), as many as
fall in the window.  The gaps are exponential, taken at evenly spaced
quantiles, so every seed gets the same gaps in another order."""
from __future__ import annotations

import math

import numpy as np

#: requests still queued at the close stay: they are due and are served
WITHDRAW_AT_CLOSE = False


def count(mix: dict, rate: float, seconds: float) -> int:
    return max(1, int(math.ceil(rate * seconds)))


def times(mix: dict, rate: float, n: int, rng: np.random.Generator
          ) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return np.cumsum(rng.permutation(-np.log1p(-q) / rate))
