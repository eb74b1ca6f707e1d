"""An offline job: the mix's ``requests`` all queued at the window's start.
What is still queued at the close was never attempted and is taken back."""
from __future__ import annotations

import numpy as np

WITHDRAW_AT_CLOSE = True


def count(mix: dict, rate: float, seconds: float) -> int:
    return int(mix["requests"])


def times(mix: dict, rate: float, n: int, rng: np.random.Generator
          ) -> np.ndarray:
    return np.zeros(n)
