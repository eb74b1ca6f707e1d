"""Flash-decode — single-token attention against a long KV cache (Pallas).

One query token per sequence attends to a KV cache of up to 512k positions
(the ``long_500k`` serve shape), reading only the blocks of ``bk``
positions that hold each sequence's ``length`` positions.

One kernel invocation walks the sequences in turn and, within each, its
live blocks, ``ceil(length / bk)`` of them: each block holds all KV heads
and is copied from HBM by a DMA started while the block before it is
folded, so the copies run back to back across blocks and sequences.  A
block is folded into online-softmax accumulators ((Hkv,G,D) f32 +
(Hkv,G,1) max/sum) in VMEM scratch, GQA folded as G query heads per KV
head, with scores and the weighted sum as products batched over the KV
heads.  Blocks past a sequence's length are neither copied nor computed,
and no step of the walk stands for them.  The per-sequence ``length``
vector and the layer index ride in SMEM by scalar prefetch.

``bk``, unless given, comes from the shapes (``block_size``).

With ``precise`` both products take each float32 operand as two bf16 parts
(``twopart.dot_general``): about float32 precision, where a plain float32
product on the chip may round its operands to bf16.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import twopart

NEG_INF = -1e30

#: the least bytes one block of K should hold: smaller blocks add a fixed
#: cost per block, larger ones read more past each sequence's length
MIN_BLOCK_BYTES = 1 << 19


def block_size(S: int, Hkv: int, D: int, itemsize: int) -> int:
    """Positions per block: the smallest power of two from 16 up that
    divides ``S`` and makes a block of K, all ``Hkv`` heads of ``D``
    elements of ``itemsize`` bytes, hold at least ``MIN_BLOCK_BYTES``; all
    of ``S`` if none under it does."""
    bk = 16
    while bk < S and (S % bk or bk * Hkv * D * itemsize < MIN_BLOCK_BYTES):
        bk *= 2
    return min(bk, S)


def live_blocks(length, bk: int):
    """``ceil(length / bk)``: the blocks of ``bk`` positions that hold a
    sequence's ``length`` positions, which the kernel reads (and block 0
    for a sequence of length 0).  Host integers and arrays, or traced
    ones."""
    return -(-length // bk)


def blocks_read(length, k):
    """What a call reads of the cache ``k`` (anything with the cache's
    ``shape`` (..., B, Hkv, S, D) and ``dtype``) for the B sequences'
    ``length``: ``(read, full, bk)``, the blocks it reads
    (``live_blocks``, summed over the sequences), the blocks a read of
    every position takes (``B * S / bk``) and its block size
    (``block_size``).  Host integers, for a counter."""
    B, Hkv, S, D = k.shape[-4:]
    bk = block_size(S, Hkv, D, jnp.dtype(k.dtype).itemsize)
    return int(live_blocks(np.asarray(length), bk).sum()), B * (S // bk), bk


def _dot(a, b, dims, precise: bool):
    if precise:
        return twopart.dot_general(a, b, dims)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# contract D, batch over the KV heads: (Hkv,G,D) x (Hkv,bk,D) -> (Hkv,G,bk)
_QK = (((2,), (2,)), ((0,), (0,)))
# contract the block's positions: (Hkv,G,bk) x (Hkv,bk,D) -> (Hkv,G,D)
_PV = (((2,), (1,)), ((0,), (0,)))


def _kernel(len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
            sem, acc_ref, m_ref, l_ref, *, scale: float, bk: int, B: int,
            precise: bool):
    layer = layer_ref[0]

    def copies(b, j, buf):
        """The DMAs of sequence ``b``'s block ``j`` into buffer ``buf``."""
        return [pltpu.make_async_copy(
                    src.at[layer, b, :, pl.ds(j * bk, bk), :],
                    dst.at[buf], sem.at[i, buf])
                for i, (src, dst) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf)))]

    def start(b, j, buf):
        for c in copies(b, j, buf):
            c.start()

    start(0, 0, 0)

    def sequence(b, step):
        length = len_ref[b]
        n = jnp.maximum(live_blocks(length, bk), 1)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        q = q_ref[b].astype(jnp.float32)                # (Hkv, G, D)

        def block(j, step):
            buf = step % 2

            @pl.when(j + 1 < n)
            def _next_block():
                start(b, j + 1, 1 - buf)

            @pl.when((j + 1 == n) & (b + 1 < B))
            def _next_sequence():
                start(b + 1, 0, 1 - buf)

            for c in copies(b, j, buf):
                c.wait()
            k = k_buf[buf].astype(jnp.float32)          # (Hkv, bk, D)
            v = v_buf[buf].astype(jnp.float32)          # (Hkv, bk, D)
            s = _dot(q, k, _QK, precise) * scale        # (Hkv, G, bk)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(kpos < length, s, NEG_INF)

            m_prev = m_ref[...]
            m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur)
            l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + _dot(p, v, _PV, precise)
            m_ref[...] = m_cur
            return step + 1

        step = jax.lax.fori_loop(0, n, block, step)
        o_ref[b] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        return step

    jax.lax.fori_loop(0, B, sequence, 0)


def decode_attention(q, k, v, length, layer=0, *, bk: Optional[int] = None,
                     precise: bool = False, interpret: bool = False):
    """q: (B,Hq,D) one token; k,v: (B,Hkv,S,D), or (L,B,Hkv,S,D) of which
    slab ``layer`` is read; attends positions < length, reading only the
    blocks of ``bk`` positions (``block_size`` if None) that hold them.

    -> (B,Hq,D)
    """
    Hkv, S, D = k.shape[-3:]
    if bk is None:
        bk = block_size(S, Hkv, D, k.dtype.itemsize)
    return _decode_attention(q, k, v, length, layer, bk=min(bk, S),
                             precise=precise, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bk", "precise", "interpret"))
def _decode_attention(q, k, v, length, layer, *, bk: int, precise: bool,
                      interpret: bool):
    if k.ndim == 4:
        k, v = k[None], v[None]
    B, Hq, D = q.shape
    _, _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    assert S % bk == 0

    qg = q.reshape(B, Hkv, G, D)
    length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    whole = pl.BlockSpec((B, Hkv, G, D), lambda i, *_: (0, 0, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole, in_hbm, in_hbm],
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM((2, Hkv, bk, D), k.dtype),
            pltpu.VMEM((2, Hkv, bk, D), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(D), bk=bk, B=B,
                          precise=precise),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="decode_attention",
    )(length, layer, qg, k, v)
    return out.reshape(B, Hq, D)
