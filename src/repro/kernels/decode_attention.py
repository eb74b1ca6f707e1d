"""Flash-decode — single-token attention against a long KV cache (Pallas).

One query token per sequence attends to a KV cache of up to 512k positions
(the ``long_500k`` serve shape): the KV sequence is the innermost sequential
grid axis, with online-softmax accumulators ((G,D) f32 + (G,1) max/sum) in
VMEM scratch, GQA folded as G query heads per KV head.  The per-sequence
``length`` vector rides in SMEM by scalar prefetch: a ``(1,)`` VMEM block of
a ``(B,)`` array is only legal for the TPU lowering when B == 1.

With ``precise`` both products take each float32 operand as two bf16 parts
(``twopart.dot_general``): about float32 precision, where a plain float32
product on the chip may round its operands to bf16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import twopart

NEG_INF = -1e30


def _dot(a, b, dims, precise: bool):
    if precise:
        return twopart.dot_general(a, b, dims)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _kernel(len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
            l_ref, *, scale: float, bk: int, n_kb: int, precise: bool):
    b = pl.program_id(0)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)             # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)             # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)             # (bk, D)
    length = len_ref[b]

    s = _dot(q, k, (((1,), (1,)), ((), ())), precise) * scale
    kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < length, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + _dot(
        p, v, (((1,), (0,)), ((), ())), precise)
    m_ref[...] = m_cur

    @pl.when(kb == n_kb - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "precise", "interpret"))
def decode_attention(q, k, v, length, layer=0, *, bk: int = 512,
                     precise: bool = False, interpret: bool = False):
    """q: (B,Hq,D) one token; k,v: (B,Hkv,S,D), or (L,B,Hkv,S,D) of which
    slab ``layer`` is read; attends positions < length.

    -> (B,Hq,D)
    """
    if k.ndim == 4:
        k, v = k[None], v[None]
    B, Hq, D = q.shape
    _, _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    bk = min(bk, S)
    assert S % bk == 0
    n_kb = S // bk
    scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, Hkv, G, D)
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    kv_spec = pl.BlockSpec((None, 1, 1, bk, D),
                           lambda b, h, kb, _, l: (l[0], b, h, kb, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, n_kb),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, kb, *_: (b, h, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, kb, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bk=bk, n_kb=n_kb,
                          precise=precise),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(length, layer, qg, k, v)
    return out.reshape(B, Hq, D)
