"""Products of float32 activations with narrower weights at about float32
precision, on an MXU that multiplies bf16.

A float32 ``x`` is cut into two parts in the weights' dtype, ``x = hi +
lo`` to about 16 significant bits, and each part is multiplied with float32
accumulation.  The weights are exact in their own dtype, so the two
products lose nothing more.  Stacking the parts on the rows lets one
product read each weight once: twice the MXU work, the same bytes, which a
memory-bound decode step does not feel.

The same functions serve plain JAX and the bodies of Pallas kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def wider(x_dtype, w_dtype) -> bool:
    """Whether activations of ``x_dtype`` are multiplied in two parts
    against weights of ``w_dtype``."""
    return jnp.dtype(x_dtype).itemsize > jnp.dtype(w_dtype).itemsize


def parts(x, dtype=jnp.bfloat16):
    """float32 ``x`` -> (hi, lo) in ``dtype``.  hi keeps x's top 16 bits,
    which bf16 holds exactly, and lo is the rest.  hi is cut out by masking
    bits, not by a round trip through bf16, which a compiler may fold
    away."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.int32(-65536), jnp.float32)
    return hi.astype(dtype), (x - hi).astype(dtype)


def stack(x, dtype):
    """(n, k) float32 -> (2n, k) in ``dtype``: the hi rows, then the lo
    rows."""
    return jnp.concatenate(parts(x, dtype), axis=0)


def join(y):
    """(2n, f) products of ``stack``ed rows -> (n, f), their sum."""
    n = y.shape[0] // 2
    return y[:n] + y[n:]


def matmul(x, w):
    """float32 ``x`` (..., k) @ narrower ``w`` (k, f) -> float32 (..., f)."""
    lead, k = x.shape[:-1], x.shape[-1]
    y = jnp.dot(stack(x.reshape(-1, k), w.dtype), w,
                preferred_element_type=jnp.float32)
    return join(y).reshape(lead + (w.shape[-1],))


def dot_general(a, b, dims):
    """float32 ``a`` and ``b`` through ``jax.lax.dot_general`` as two bf16
    parts each: the three products that matter, each exact with float32
    accumulation."""
    (ah, al), (bh, bl) = parts(a), parts(b)
    d = functools.partial(jax.lax.dot_general, dimension_numbers=dims,
                          preferred_element_type=jnp.float32)
    return d(ah, bh) + (d(al, bh) + d(ah, bl))
