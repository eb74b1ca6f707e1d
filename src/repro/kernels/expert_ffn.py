"""Held-experts SwiGLU FFN for one decode step (Pallas).

Every token of the step goes through every expert this chip holds, scaled
by its gate for that expert, which is 0 where the router did not send it:

    out = sum_e gate[:, e] * (silu(x @ wg[e]) * (x @ wi[e])) @ wo[e]

At decode an expert takes a token at most once, so the B tokens of the step
are a row budget that can never overflow: no routed token is dropped.  The
grid ``(E, F // tf)`` walks the held experts and the d_ff tiles of each;
every block shape and every DMA follows from the shapes (B, E, d_model,
d_ff) alone, never from the routing or from which slots are occupied, so
each expert's ``wg``, ``wi`` and ``wo`` are read exactly once a call and the
call takes the same time whatever the router chose.

The weights may be the ``(L, E, D, F)`` stacks of every layer: the layer
index rides in SMEM by scalar prefetch and picks the layer's slab where it
lies, so the layer scan copies no weights out of the stack.  The output
block stays in VMEM for the whole grid and accumulates in float32.

Tokens wider than the weights (float32 against bf16) are multiplied at
about float32 precision: they enter as their two parts stacked on the rows
(``twopart.stack``), every product takes both in one pass over the weight
block (twice the MXU work, the same bytes), and the SwiGLU output is cut
likewise before ``wo``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import twopart

#: d_ff tile: the largest multiple of 128 that divides d_ff, up to this
MAX_TF = 640


def _tile(f: int) -> int:
    for tf in range(min(MAX_TF, f) // 128 * 128, 0, -128):
        if f % tf == 0:
            return tf
    return f


def _dot(x, w, split: bool):
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return twopart.join(y) if split else y


def _kernel(layer_ref, x_ref, g_ref, wg_ref, wi_ref, wo_ref, o_ref, *,
            split: bool):
    del layer_ref                       # used by the index maps only
    e = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((e == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                                 # (B, D), or (2B, D)
    a = _dot(x, wg_ref[...], split)
    b = _dot(x, wi_ref[...], split)
    g = g_ref[...]                                       # (B, E) f32
    ids = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    gate = jnp.sum(jnp.where(ids == e, g, 0.0), axis=1, keepdims=True)
    h = a / (1.0 + jnp.exp(-a)) * b * gate               # (B, tf) f32
    dt = wo_ref.dtype
    h = twopart.stack(h, dt) if split else h.astype(dt)
    o_ref[...] += _dot(h, wo_ref[...], split)


@functools.partial(jax.jit, static_argnames=("tf", "interpret"))
def expert_ffn(x, gates, wg, wi, wo, layer=0, *, tf=None,
               interpret: bool = False):
    """x: (B, D) tokens, in the weights' dtype or wider; gates: (B, E)
    float32, each token's weight for each held expert (0 where not
    routed); wg, wi: (E, D, F) and wo: (E, F, D), or (L, E, ...) stacks of
    which slab ``layer`` is read.

    -> (B, D) float32
    """
    if wg.ndim == 3:
        wg, wi, wo = wg[None], wi[None], wo[None]
    split = twopart.wider(x.dtype, wg.dtype)
    x = twopart.stack(x, wg.dtype) if split else x.astype(wg.dtype)
    B, E = gates.shape
    D = x.shape[1]
    _, _, _, F = wg.shape
    tf = tf or _tile(F)
    assert F % tf == 0, (F, tf)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    whole = lambda e, j, l: (0, 0)                        # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(E, F // tf),
        in_specs=[
            pl.BlockSpec(x.shape, whole),
            pl.BlockSpec((B, E), whole),
            pl.BlockSpec((None, None, D, tf),
                         lambda e, j, l: (l[0], e, 0, j)),
            pl.BlockSpec((None, None, D, tf),
                         lambda e, j, l: (l[0], e, 0, j)),
            pl.BlockSpec((None, None, tf, D),
                         lambda e, j, l: (l[0], e, j, 0)),
        ],
        out_specs=pl.BlockSpec((B, D), whole),
    )
    return pl.pallas_call(
        functools.partial(_kernel, split=split),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="expert_ffn",
    )(layer, x, gates.astype(jnp.float32), wg, wi, wo)
