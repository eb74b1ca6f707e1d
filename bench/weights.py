"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout ``repro.models.Model`` takes (the system's input
format): per-layer leaves stacked on a leading layer axis.  The values are
the benchmark's own, so the plain reference reads the same arrays the
server is given and nothing the server made.  Every leaf is drawn, biases
and norm scales included, so a path that drops one of them changes the
logits.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: leaf path -> (shape, kind); kinds pick the distribution in ``_draw``.
#: Each family file's ``layout(c)`` gives its model's.
Layout = Dict[str, Tuple[Tuple[int, ...], str]]


def _draw(key, shape, kind):
    f32 = jnp.float32
    if kind == "matrix":          # fan-in scaled, fan-in is the second-last
        return jax.random.normal(key, shape, f32) / math.sqrt(shape[-2])
    if kind == "embed":
        return jax.random.normal(key, shape, f32)
    if kind == "scale":           # norm scales and D: about one
        return 1.0 + 0.1 * jax.random.normal(key, shape, f32)
    if kind == "bias":
        return 0.1 * jax.random.normal(key, shape, f32)
    if kind == "conv":            # depthwise taps, fan-in = width
        return jax.random.normal(key, shape, f32) / math.sqrt(shape[-2])
    if kind == "a_log":           # A in [1, 16] as Mamba2 initialises it
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if kind == "dt_bias":         # softplus^-1 of dt in [1e-3, 1e-1], log-uniform
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(kind)


def _nest(flat: Dict[str, jax.Array]) -> dict:
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


def key_for(seed: int) -> jax.Array:
    """A threefry key from any integer seed (wider than 32 bits too)."""
    words = np.random.SeedSequence(int(seed) % 2**64).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32))


def make(layout: Layout, seed: int, dtype) -> dict:
    """All leaves of ``layout`` in ``dtype``, in one jitted call."""
    paths = sorted(layout)

    @jax.jit
    def build(key):
        return {p: _draw(jax.random.fold_in(key, i), *layout[p]).astype(dtype)
                for i, p in enumerate(paths)}

    return _nest(build(key_for(seed)))


def flat(tree: dict, prefix: str = "") -> Dict[str, Tuple[tuple, str]]:
    """path -> (shape, dtype name) of a nested tree of arrays or shapes."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat(v, p + "/"))
        else:
            out[p] = (tuple(v.shape), str(v.dtype))
    return out
