"""The comparison that decides ``correct``.

After the window, a sample of the requests the server finished (drawn from
the seed, the longest always in it) is run once through the plain float32
reference: each prompt followed by its served tokens.  For every served
token the reference's logits at the position that produced it give the
*gap*: how far the served token's logit lies below the reference's best.
A correct greedy server only loses near-ties to rounding, so its widest gap
is small; the limit in the cell file is set between that reading and the
control's.

The control is the same reference with every weight matrix product in fp8
e4m3, the precision below the configuration's bf16 (weights scaled per
output column and activations per row, to the format's largest finite
value).  At each position of the same sequences the control's own first
choice is read against the float32 reference.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from loadgen import rng_for

ROW_BLOCK = 256


def mm_f32(x, w):
    return jnp.matmul(x, w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _fp8(a, axis):
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32), scale


def mm_fp8(x, w):
    """Weights per output column and activations per row in fp8 e4m3."""
    wq, sw = _fp8(w.astype(jnp.float32), axis=-2)
    xq, sx = _fp8(x, axis=-1)
    return jnp.matmul(xq, wq, precision=jax.lax.Precision.HIGHEST) * sx * sw


CONTROLS = {"fp8": mm_fp8}


@dataclasses.dataclass
class Served:
    """One finished request as the client saw it."""
    prompt: List[int]
    output: List[int]

    @property
    def tokens(self) -> int:
        return len(self.prompt) + len(self.output)


def sample(finished: Sequence[Served], k: int, seed: int) -> List[Served]:
    """``k`` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    longest = max(range(len(finished)), key=lambda i: finished[i].tokens)
    rest = [i for i in range(len(finished)) if i != longest]
    pick = rng_for(seed, 5).permutation(rest)[:max(0, k - 1)]
    return [finished[i] for i in [longest, *sorted(pick.tolist())]]


def _rows(requests: Sequence[Served], pad_to: int):
    """Token matrix (N, pad_to) and, per served token, (row, position, id)."""
    toks = np.zeros((len(requests), pad_to), np.int32)
    where = []
    for n, r in enumerate(requests):
        seq = r.prompt + r.output[:-1]
        toks[n, :len(seq)] = seq
        for j, t in enumerate(r.output):
            where.append((n, len(r.prompt) - 1 + j, t))
    return toks, np.asarray(where, np.int64).reshape(-1, 3)


def gaps(family, weights: dict, c: dict, requests: Sequence[Served],
         pad_to: int, control: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Per served token: ``served`` gap, and with ``control`` (a key of
    ``CONTROLS``) the gap of that reference's first choice, both against the
    float32 reference."""
    toks, where = _rows(requests, pad_to)
    T = len(where)
    where = np.pad(where, ((0, (-T) % ROW_BLOCK), (0, 0)))
    w_out = family.unembed(weights, c)

    @functools.partial(jax.jit, static_argnums=2)
    def hidden(w, t, mm_name):
        mm = CONTROLS[mm_name] if mm_name else mm_f32
        return family.reference_hidden(w, t, c, mm)

    @functools.partial(jax.jit, static_argnums=4)
    def block_gaps(h_ref, h_ctl, at, w_out, mm_name):
        logits = mm_f32(h_ref[at[:, 0], at[:, 1]], w_out)
        best = logits.max(axis=-1)
        served = best - jnp.take_along_axis(logits, at[:, 2:], 1)[:, 0]
        if not mm_name:
            return served, served
        pick = jnp.argmax(CONTROLS[mm_name](h_ctl[at[:, 0], at[:, 1]],
                                            w_out), -1)
        return served, best - jnp.take_along_axis(logits, pick[:, None],
                                                  1)[:, 0]

    with jax.default_matmul_precision("highest"):
        h_ref = hidden(weights, jnp.asarray(toks), None)
        h_ctl = hidden(weights, jnp.asarray(toks), control) if control \
            else h_ref
        out_s, out_c = [], []
        for b in range(0, len(where), ROW_BLOCK):
            s, g = block_gaps(h_ref, h_ctl,
                              jnp.asarray(where[b:b + ROW_BLOCK], jnp.int32),
                              w_out, control)
            out_s.append(np.asarray(s))
            out_c.append(np.asarray(g))
    res = {"served": np.concatenate(out_s)[:T]}
    if control:
        res["control"] = np.concatenate(out_c)[:T]
    return res


def verdict(widest: Optional[float], limit: float, n_tokens: int,
            min_tokens: int) -> bool:
    return (widest is not None and np.isfinite(widest)
            and n_tokens >= min_tokens and widest <= limit)
