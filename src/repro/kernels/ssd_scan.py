"""Mamba2 SSD (state-space duality) chunk scan — Pallas TPU kernel.

One grid step processes one (batch, head, chunk) cell: the intra-chunk
quadratic term runs on the MXU ((Q,Q) and (Q,P) matmuls in VMEM), and the
inter-chunk state recurrence is carried in a (P,N) f32 VMEM scratch across
the innermost (sequential) chunk grid axis — the TPU-native replacement for
the parallel-prefix formulation GPU implementations use (DESIGN.md §2).

Operands are laid out head-major so that every block's last two dims are
(8,128)-tileable or span the whole array dim; the per-head scalars ``A`` and
``D`` live in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(A_ref, D_ref, x_ref, dtc_ref, dtr_ref, B_ref, C_ref, y_ref,
            state_out_ref, state_ref, *, Q: int, n_chunks: int):
    h = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)             # (Q, P)
    A = A_ref[h]                                    # scalar (SMEM)
    Dv = D_ref[h]                                   # scalar (SMEM)
    dt = dtc_ref[0, 0].astype(jnp.float32)          # (Q, 1)
    a_col = dt * A                                  # (Q, 1)
    a_row = dtr_ref[0, 0].astype(jnp.float32) * A   # (1, Q)
    Bm = B_ref[0].astype(jnp.float32)               # (Q, N)
    Cm = C_ref[0].astype(jnp.float32)               # (Q, N)

    # inclusive cumsum of a, as a column and as a row, by masked reductions
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tril = row >= col
    cum = jnp.sum(jnp.where(tril, a_row, 0.0), axis=1,
                  keepdims=True)                    # (Q, 1)
    cum_row = jnp.sum(jnp.where(row <= col, a_col, 0.0), axis=0,
                      keepdims=True)                # (1, Q)
    total = jnp.sum(a_row, axis=1, keepdims=True)   # (1, 1)
    Lmat = jnp.exp(jnp.where(tril, cum - cum_row, -jnp.inf))

    xdt = x * dt                                    # (Q, P)
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    y_diag = jax.lax.dot_general(G * Lmat, xdt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    state = state_ref[...]                          # (P, N)
    y_off = jnp.exp(cum) * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)         # (Q, P)

    decay_to_end = jnp.exp(total - cum)             # (Q, 1)
    new_contrib = jax.lax.dot_general(
        xdt * decay_to_end, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # (P, N)
    state_ref[...] = state * jnp.exp(total) + new_contrib

    y_ref[0, 0] = (y_diag + y_off + Dv * x).astype(y_ref.dtype)

    @pl.when(c == n_chunks - 1)
    def _flush():
        state_out_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk: int = 128,
             interpret: bool = False):
    """x: (B,L,H,P); dt: (B,L,H); A,D: (H,); Bm,Cm: (B,L,N)
    -> (y (B,L,H,P), final_state (B,H,P,N) f32)."""
    B_, L, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0
    n_chunks = L // Q

    # head-major operands: every block's last two dims are then
    # (chunk, full) or (full, chunk), as the TPU tiling requires
    xh = x.transpose(0, 2, 1, 3)                    # (B, H, L, P)
    dth = dt.transpose(0, 2, 1)                     # (B, H, L)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_kernel, Q=Q, n_chunks=n_chunks)
    y, state = pl.pallas_call(
        kernel,
        grid=(B_, H, n_chunks),
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xh.shape, x.dtype),
            jax.ShapeDtypeStruct((B_, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(A.astype(jnp.float32), D.astype(jnp.float32), xh,
      dth[..., None], dth[:, :, None, :], Bm, Cm)
    return y.transpose(0, 2, 1, 3), state
