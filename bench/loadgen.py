"""Seeded traffic: arrivals from a process found by name, with clipped
lognormal prompt and output lengths.

A mix file holds only parameters::

    {"arrivals": "poisson",                    # bench/arrivals/<name>.py
     "prompt": {"median": 128, "sigma": 0.6, "min": 32, "max": 384},
     "output": {"median": 48,  "sigma": 0.6, "min": 16, "max": 128},
     "requests": 48}                           # what the process reads

An arrival process is a file ``bench/arrivals/<name>.py`` with
``count(mix, rate, seconds)``, ``times(mix, rate, n, rng)`` (arrival
offsets from the window's start, in order) and ``WITHDRAW_AT_CLOSE`` (take
back what is still queued when the window closes).  The rate of a mix is
the cell's (``rate_rps`` in the cell file).  Every seed gets the same
multiset of prompt and output lengths, taken at evenly spaced quantiles of
their distributions; the seed only orders them and draws the token ids.  So
two seeds offer the same work, and a run's spread is the system's, not the
sample's.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import statistics
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()
ARRIVALS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "arrivals")


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the client will send it."""
    index: int
    arrival_s: float        # scheduled arrival, from the window's start
    prompt: List[int]
    max_new_tokens: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed works."""
    return np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed) % 2**64, stream])))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` clipped lognormal lengths at evenly spaced quantiles, shuffled."""
    z = np.array([_NORMAL.inv_cdf(q) for q in _quantiles(n)])
    raw = dist["median"] * np.exp(dist["sigma"] * z)
    out = np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)
    return rng.permutation(out)


def arrivals(mix: dict):
    """The arrival process ``bench/arrivals/<mix["arrivals"]>.py``."""
    name = mix["arrivals"]
    path = os.path.join(ARRIVALS_DIR, f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"unknown arrivals {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_arrivals_" + "".join(ch if ch.isalnum() else "_"
                                    for ch in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def plan(mix: dict, *, rate: float, seconds: float, seed: int,
         vocab: int) -> List[Planned]:
    """The requests of one run, in arrival order."""
    process = arrivals(mix)
    n = process.count(mix, rate, seconds)
    p_len = lengths(mix["prompt"], n, rng_for(seed, 1))
    o_len = lengths(mix["output"], n, rng_for(seed, 2))
    at = process.times(mix, rate, n, rng_for(seed, 3))
    tok = rng_for(seed, 4)
    return [Planned(index=i, arrival_s=float(at[i]),
                    prompt=tok.integers(0, vocab, int(p_len[i])).tolist(),
                    max_new_tokens=int(o_len[i]))
            for i in range(n)]


def p99_ms(lateness_s: List[float]) -> float:
    """99th percentile of how late the client sent, in ms (0 if none)."""
    if not lateness_s:
        return 0.0
    return 1e3 * float(np.percentile(np.asarray(lateness_s), 99))
