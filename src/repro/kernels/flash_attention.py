"""Flash attention forward — Pallas TPU kernel.

TPU-native adaptation of the FlashAttention blocking scheme: online softmax
with the KV dimension as the innermost (sequential) grid axis, per-(head,
q-block) f32 accumulators held in VMEM scratch across KV steps, MXU-aligned
(multiple-of-128) block shapes, GQA handled by an index_map that maps G
query heads onto one KV head (no jnp.repeat materialization).

Supports causal masking and sliding windows (gemma3-style local layers).
Validated in interpret mode against ``ref.mha_ref``; on TPU it is selected
with ``ModelOpts(use_kernel=True)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            n_kb: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)           # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)           # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)           # (bk, d)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (bq, bk)

    qpos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask = kpos <= qpos
    if window:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # (bq, 1)
    m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)                         # (bq, bk)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(kb == n_kb - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False):
    """q: (B,Hq,Sq,D); k,v: (B,Hkv,Sk,D) -> (B,Hq,Sq,D)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    G = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    n_qb, n_kb = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(D)

    grid = (B, Hq, n_qb, n_kb)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, bq=bq, bk=bk,
        n_kb=n_kb)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qb, kb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qb, kb, G=G: (b, h // G, kb, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, qb, kb, G=G: (b, h // G, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, qb, kb: (b, h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
