"""Kernel config-space domain + timing harness for multi-fidelity search.

The framework's own hot kernels as a search problem: block sizes / grid
shapes of :mod:`repro.kernels.flash_attention`, ``decode_attention`` and
``ssd_scan`` become a hierarchical :class:`~repro.core.domain.Domain`
(one provider per kernel, exactly like ``tuner/strategies.
sharding_domain`` treats parallelism families), and two registered
objectives form the ``kernel`` fidelity ladder:

``kernel_analytic`` (rung 0)
    A grid-shape cost sketch — microseconds, no execution.  In
    interpret mode (the CPU emulator) wall time is dominated by
    per-grid-step interpreter overhead, so fewer/larger blocks win;
    the model scores exactly that trade.
``kernel_time`` (top rung)
    Measured wall time of the interpret-mode kernel in microseconds,
    via :func:`time_fn` — the *fixed* harness (synchronized warm-up,
    ``perf_counter``, median-of-reps) that ``benchmarks/kernels.py``
    also uses.  Both rungs score absolute microseconds (the analytic
    rung scales its element count by a nominal throughput), so the
    three kernels rank inside one search and a prefilter can
    calibrate probe against truth; the jnp-reference ratio stays in
    the result payload as a diagnostic only — reference costs differ
    wildly per kernel and would wreck cross-provider ranking if they
    normalized the value.

Shapes are named *presets* so unit content keys stay scalar: the preset
name is the identity, the shape tuples live here.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, Tuple

from repro.core.domain import Domain, ParamSpace, ProviderSpace


def time_fn(fn, *args, reps: int = 5) -> float:
    """Median wall time of ``fn(*args)`` in microseconds.

    The pitfalls this harness exists to avoid (both shipped in the
    original ``benchmarks/kernels.py``): the warm-up call is
    synchronized with ``block_until_ready`` so no async-dispatched
    work leaks into the timed region, each rep is timed individually
    with the monotonic ``time.perf_counter`` (``time.time`` is
    wall-clock, low-resolution, and can step backwards), and the
    median — not the mean — is reported so one scheduler hiccup
    cannot skew a rung's ground truth.
    """
    import jax
    jax.block_until_ready(fn(*args))        # compile + retire warm-up
    times = []
    for _ in range(int(reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    n = len(times)
    mid = n // 2
    med = times[mid] if n % 2 else 0.5 * (times[mid - 1] + times[mid])
    return med * 1e6


#: preset -> per-kernel shape tuples.  "tiny" keeps a CI --quick sweep
#: in seconds; "small" is the committed BENCH_fidelity.json ground
#: truth.  All sequence lengths are powers of two so every block-size
#: value divides evenly (the kernels assert divisibility).
PRESETS: Dict[str, Dict[str, Tuple[int, ...]]] = {
    # flash: (B, Hq, Hkv, S, D); decode: (B, Hq, Hkv, S, D, length);
    # ssd: (B, L, H, P, N)
    "tiny": {
        "flash_attention": (1, 2, 1, 128, 32),
        "decode_attention": (1, 2, 1, 256, 32, 200),
        "ssd_scan": (1, 128, 1, 16, 16),
    },
    "small": {
        "flash_attention": (1, 4, 2, 256, 64),
        "decode_attention": (1, 4, 2, 1024, 64, 1000),
        "ssd_scan": (1, 256, 2, 32, 32),
    },
}

#: per-preset block-size values; index 0 is the incumbent/default
#: (model-based BBOs seed it first — the sharding_domain convention)
_BLOCKS: Dict[str, Dict[str, Tuple[int, ...]]] = {
    "tiny": {
        "flash": (128, 64, 32),
        "decode": (256, 128, 64),
        "ssd": (128, 64, 32),
    },
    "small": {
        "flash": (128, 256, 64),
        "decode": (512, 256, 128),
        "ssd": (128, 64, 32),
    },
}


def kernel_domain(preset: str = "small") -> Domain:
    """The kernel autotuning search space for one shape preset: one
    provider per kernel, block sizes as categorical parameters."""
    if preset not in PRESETS:
        raise KeyError(
            f"unknown kernel preset {preset!r}; knows {sorted(PRESETS)}")
    blocks = _BLOCKS[preset]
    return Domain(providers=(
        ProviderSpace("flash_attention", (
            ParamSpace("bq", blocks["flash"]),
            ParamSpace("bk", blocks["flash"]))),
        ProviderSpace("decode_attention", (
            ParamSpace("bk", blocks["decode"]),)),
        ProviderSpace("ssd_scan", (
            ParamSpace("chunk", blocks["ssd"]),)),
    ))


@functools.lru_cache(maxsize=None)
def _inputs(provider: str, preset: str):
    """Deterministic kernel inputs per (provider, preset), built once
    per process (forked workers inherit them for free)."""
    import jax
    import jax.numpy as jnp
    rng = jax.random.PRNGKey(0)
    shape = PRESETS[preset][provider]
    if provider == "flash_attention":
        B, Hq, Hkv, S, D = shape
        ks = jax.random.split(rng, 3)
        return (jax.random.normal(ks[0], (B, Hq, S, D), jnp.float32),
                jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32),
                jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32))
    if provider == "decode_attention":
        B, Hq, Hkv, S, D, _length = shape
        ks = jax.random.split(rng, 3)
        return (jax.random.normal(ks[0], (B, Hq, D), jnp.float32),
                jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32),
                jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32))
    if provider == "ssd_scan":
        B, L, H, P, N = shape
        ks = jax.random.split(rng, 5)
        import jax.nn
        return (jax.random.normal(ks[0], (B, L, H, P)) * 0.5,
                jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))) * 0.5,
                -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3),
                jax.random.normal(ks[3], (B, L, N)) * 0.3,
                jax.random.normal(ks[4], (B, L, N)) * 0.3,
                jnp.ones((H,)))
    raise KeyError(f"unknown kernel provider {provider!r}")


def _kernel_fn(provider: str, preset: str, config: Dict[str, Any]):
    """(callable, args) for one candidate — interpret mode everywhere:
    the domain transfers block shapes to TPU, the measurement validates
    the trade on the emulator."""
    from repro.kernels import ops
    args = _inputs(provider, preset)
    if provider == "flash_attention":
        bq, bk = int(config["bq"]), int(config["bk"])
        return (lambda q, k, v: ops.flash_attention(
            q, k, v, causal=True, bq=bq, bk=bk, interpret=True)), args
    if provider == "decode_attention":
        bk = int(config["bk"])
        length = PRESETS[preset][provider][5]
        return (lambda q, k, v: ops.decode_attention(
            q, k, v, length, bk=bk, interpret=True)), args
    if provider == "ssd_scan":
        chunk = int(config["chunk"])
        return (lambda *a: ops.ssd(*a, chunk=chunk, interpret=True)[0]), args
    raise KeyError(f"unknown kernel provider {provider!r}")


@functools.lru_cache(maxsize=None)
def _ref_us(provider: str, preset: str, reps: int) -> float:
    """Reference (jnp oracle) timing, measured once per process."""
    from repro.kernels.ref import decode_mha_ref, mha_ref, ssd_ref
    args = _inputs(provider, preset)
    if provider == "flash_attention":
        fn = lambda q, k, v: mha_ref(q, k, v, causal=True)    # noqa: E731
    elif provider == "decode_attention":
        length = PRESETS[preset][provider][5]
        fn = lambda q, k, v: decode_mha_ref(                  # noqa: E731
            q, k, v, length=length)
    elif provider == "ssd_scan":
        fn = lambda *a: ssd_ref(*a, chunk=128)[0]             # noqa: E731
    else:
        raise KeyError(f"unknown kernel provider {provider!r}")
    return time_fn(fn, *args, reps=reps)


def grid_steps(provider: str, preset: str, config: Dict[str, Any]) -> int:
    """Number of pallas grid steps one candidate launches (for
    ``decode_attention``, the blocks its one invocation walks) — the
    quantity interpret-mode wall time is proportional to."""
    shape = PRESETS[preset][provider]
    if provider == "flash_attention":
        B, Hq, _Hkv, S, _D = shape
        return B * Hq * (S // int(config["bq"])) * (S // int(config["bk"]))
    if provider == "decode_attention":
        # one invocation walks each sequence's live blocks
        B, _Hq, _Hkv, _S, _D, length = shape
        return B * -(-length // int(config["bk"]))
    if provider == "ssd_scan":
        B, L, H, _P, _N = shape
        return B * H * (L // int(config["chunk"]))
    raise KeyError(f"unknown kernel provider {provider!r}")


#: interpreter overhead per grid step, measured in block-elements of
#: useful work — the single constant the analytic rung trades against
_STEP_OVERHEAD_ELEMS = 4096.0

#: nominal interpreter throughput scaling the analytic element count
#: to microseconds — only the *scale* of the low rung, never its
#: ranking, so precision is irrelevant (prefilters recalibrate anyway)
_ELEMS_PER_US = 64.0


def _work_elems(provider: str, preset: str) -> float:
    """Total elements of useful work, block-shape independent."""
    shape = PRESETS[preset][provider]
    if provider == "flash_attention":
        B, Hq, _Hkv, S, _D = shape
        return float(B * Hq * S * S)
    if provider == "decode_attention":
        B, Hq, _Hkv, S, D, _length = shape
        return float(B * Hq * S * D)
    if provider == "ssd_scan":
        B, L, _H, P, N = shape
        return float(B * L * (P + N))
    raise KeyError(f"unknown kernel provider {provider!r}")


def eval_kernel_analytic(params: Dict[str, Any],
                         context: Dict[str, Any]) -> dict:
    """Rung 0 of the kernel ladder: estimated interpret-mode wall time
    ``(work + overhead·steps) / throughput`` microseconds — no
    execution, deterministic.  Absolute (work included), not
    per-element: a relative score would erase the real cross-kernel
    cost differences the search must rank."""
    provider, preset = params["provider"], params["preset"]
    config = dict(params["config"])
    steps = grid_steps(provider, preset, config)
    work = _work_elems(provider, preset)
    value = (work + _STEP_OVERHEAD_ELEMS * steps) / _ELEMS_PER_US
    return {"value": float(value), "grid_steps": int(steps)}


def eval_kernel_time(params: Dict[str, Any],
                     context: Dict[str, Any]) -> dict:
    """Top rung of the kernel ladder: measured interpret-mode wall time
    of the candidate in microseconds, plus the jnp-reference ratio (a
    diagnostic, not the value — per-kernel reference costs differ too
    much to normalize by) and the max |err| against the oracle (a
    fast-but-wrong block shape must be visible)."""
    import jax.numpy as jnp
    provider, preset = params["provider"], params["preset"]
    reps = int(params.get("reps", 5))
    config = dict(params["config"])
    fn, args = _kernel_fn(provider, preset, config)
    kernel_us = time_fn(fn, *args, reps=reps)
    ref_us = _ref_us(provider, preset, reps)
    from repro.kernels.ref import decode_mha_ref, mha_ref, ssd_ref
    if provider == "flash_attention":
        oracle = mha_ref(*args, causal=True)
    elif provider == "decode_attention":
        length = PRESETS[preset][provider][5]
        oracle = decode_mha_ref(*args, length=length)
    else:
        oracle = ssd_ref(*args, chunk=128)[0]
    maxerr = float(jnp.max(jnp.abs(fn(*args) - oracle)))
    return {"value": float(kernel_us),
            "kernel_us": float(kernel_us), "ref_us": float(ref_us),
            "ratio": float(kernel_us / ref_us), "maxerr": maxerr}
