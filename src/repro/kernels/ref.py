"""Pure-jnp oracles for every Pallas kernel (CPU ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def mha_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B,Hq,Sq,D); k,v: (B,Hkv,Sk,D) -> (B,Hq,Sq,D).  Dense softmax."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    kq = jnp.repeat(k, G, axis=1)
    vq = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32),
                   kq.astype(jnp.float32)) / math.sqrt(D)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(k.shape[2])[None, :]
    m = jnp.ones((Sq, k.shape[2]), bool)
    if causal:
        m = kpos <= qpos
    if window:
        m = m & (kpos > qpos - window)
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqs,bhsd->bhqd", p, vq.astype(jnp.float32)
                      ).astype(q.dtype)


def decode_mha_ref(q, k, v, *, length=None):
    """q: (B,Hq,D); k,v: (B,Hkv,S,D); attends to positions < length."""
    B, Hq, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = Hq // Hkv
    kq = jnp.repeat(k, G, axis=1)
    vq = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                   kq.astype(jnp.float32)) / math.sqrt(D)
    if length is not None:
        s = jnp.where(jnp.arange(S)[None, None] < length, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p, vq.astype(jnp.float32)
                      ).astype(q.dtype)


def ssd_ref(x, dt, A, Bm, Cm, D, chunk: int):
    """Delegates to the model-layer chunked SSD reference (same math)."""
    from repro.models.ssm import ssd_reference
    return ssd_reference(x, dt, A, Bm, Cm, D, chunk)


def expert_ffn_ref(x, gates, wg, wi, wo):
    """x: (B,D); gates: (B,E); wg, wi: (E,D,F); wo: (E,F,D) -> (B,D) f32.
    One expert at a time: each token's SwiGLU output through it, times the
    token's gate for it."""
    f32 = jnp.float32
    out = jnp.zeros(x.shape, f32)
    for e in range(wg.shape[0]):
        h = jax.nn.silu(x.astype(f32) @ wg[e].astype(f32)) * \
            (x.astype(f32) @ wi[e].astype(f32))
        out = out + gates[:, e:e + 1] * (h @ wo[e].astype(f32))
    return out
