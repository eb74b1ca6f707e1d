"""Mamba2 (SSD — state-space duality) block.

The reference forward is the chunked SSD algorithm from the Mamba2 paper,
restructured as a ``lax.scan`` over sequence chunks so the only transient
buffer is one (B, H, Q, Q) intra-chunk decay matrix per step (never the
(B, H, C, Q, Q) all-chunks tensor).  ``repro.kernels.ssd_scan`` is the Pallas
TPU kernel for the same computation and is validated against this oracle.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distrib.logical import P, ShardCtx
from repro.models.layers import rmsnorm, rmsnorm_spec


def mamba_spec(cfg: ArchConfig) -> dict:
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_dim = di + 2 * n
    return {
        # in_proj -> [z (di), xBC (di + 2n), dt (h)]
        "in_proj": P((d, 2 * di + 2 * n + h), ("embed", "inner")),
        "conv_w": P((cfg.ssm_conv_width, conv_dim), ("conv", "inner"),
                    scale=0.5),
        "conv_b": P((conv_dim,), ("inner",), init="zeros"),
        "A_log": P((h,), ("ssm_heads",), init="ones"),
        "D": P((h,), ("ssm_heads",), init="ones"),
        "dt_bias": P((h,), ("ssm_heads",), init="zeros"),
        "norm": rmsnorm_spec(di),
        "out_proj": P((di, d), ("inner", "embed")),
    }


def _split_proj(cfg: ArchConfig, zxbcdt: jax.Array):
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:2 * di + 2 * n]
    dt = zxbcdt[..., 2 * di + 2 * n:]
    assert dt.shape[-1] == h
    return z, xBC, dt


def _causal_conv(xBC: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv, width W.  xBC: (B, L, C); w: (W, C)."""
    W = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC)
    L = xBC.shape[1]
    for i in range(W):
        out = out + pad[:, i:i + L] * w[i].astype(xBC.dtype)
    return jax.nn.silu(out + b.astype(xBC.dtype))


def _segsum(a: jax.Array) -> jax.Array:
    """a: (..., Q) -> lower-triangular cumulative segment sums (..., Q, Q)."""
    Q = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    d = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, d, -jnp.inf)


def ssd_reference(
    x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array, Cm: jax.Array,
    D: jax.Array, chunk: int,
    init_state: jax.Array = None,
    ctx: ShardCtx = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD.

    x:  (B, L, H, P)   inputs per head
    dt: (B, L, H)      positive step sizes (already softplus'ed + bias)
    A:  (H,)           negative decay rates
    Bm, Cm: (B, L, N)  input/output state projections (shared across heads)
    Returns (y (B,L,H,P), final_state (B,H,P,N)).
    """
    B_, L, H, Pd = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    n = L // Q

    a = (dt * A.astype(jnp.float32)[None, None, :]).astype(jnp.float32)
    xw = (x.astype(jnp.float32) * dt[..., None])
    if ctx is not None:
        a = ctx.constrain(a, "batch", "seq", "ssm_heads")
        xw = ctx.constrain(xw, "batch", "seq", "ssm_heads", "ssm_hd")

    def chunk_of(t, i):
        return t.reshape((B_, n, Q) + t.shape[2:])[:, i]

    a_c = a.reshape(B_, n, Q, H)
    xw_c = xw.reshape(B_, n, Q, H, Pd)
    B_c = Bm.astype(jnp.float32).reshape(B_, n, Q, N)
    C_c = Cm.astype(jnp.float32).reshape(B_, n, Q, N)

    if init_state is None:
        init_state = jnp.zeros((B_, H, Pd, N), jnp.float32)

    def body(state, xs):
        ac, xc, bc, cc = xs           # (B,Q,H), (B,Q,H,P), (B,Q,N), (B,Q,N)
        ah = ac.transpose(0, 2, 1)    # (B,H,Q)
        cum = jnp.cumsum(ah, axis=-1)                       # (B,H,Q)
        Lmat = jnp.exp(_segsum(ah))                         # (B,H,Q,Q)
        G = jnp.einsum("bqn,bsn->bqs", cc, bc)              # (B,Q,Q)
        M = G[:, None] * Lmat                               # (B,H,Q,Q)
        y_diag = jnp.einsum("bhqs,bshp->bqhp", M, xc)
        # contribution of the carried state
        state_decay = jnp.exp(cum)                          # (B,H,Q)
        y_off = jnp.einsum("bqn,bhpn,bhq->bqhp", cc, state, state_decay)
        # update carried state
        total = cum[..., -1]                                # (B,H)
        decay_to_end = jnp.exp(cum[..., -1:] - cum)         # (B,H,Q)
        new_contrib = jnp.einsum("bqn,bhq,bqhp->bhpn",
                                 bc, decay_to_end, xc)
        state = state * jnp.exp(total)[..., None, None] + new_contrib
        return state, y_diag + y_off

    xs = (a_c.transpose(1, 0, 2, 3), xw_c.transpose(1, 0, 2, 3, 4),
          B_c.transpose(1, 0, 2, 3), C_c.transpose(1, 0, 2, 3))
    # remat the chunk body: backward recomputes the (B,H,Q,Q) intra-chunk
    # matrices per chunk instead of saving them for all chunks at once.
    state, ys = jax.lax.scan(jax.checkpoint(body), init_state, xs)
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B_, L, H, Pd)
    y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), state


def mamba_block(p, x: jax.Array, cfg: ArchConfig, ctx: ShardCtx,
                use_kernel: bool = False) -> jax.Array:
    """Full Mamba2 mixer (train/prefill path).  x: (B, L, D_model)."""
    dt_ = x.dtype
    B_, L, _ = x.shape
    di, n, h, pd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim

    zxbcdt = x @ p["in_proj"].astype(dt_)
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    xBC = ctx.constrain(_causal_conv(xBC, p["conv_w"], p["conv_b"]),
                        "batch", "seq", "inner")
    xs = xBC[..., :di].reshape(B_, L, h, pd)
    Bm = xBC[..., di:di + n]
    Cm = xBC[..., di + n:]
    dt = jax.nn.softplus(
        dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    if use_kernel:
        from repro.kernels import ops as kops
        y, _ = kops.ssd(xs, dt, A, Bm, Cm, p["D"], chunk=cfg.ssm_chunk)
    else:
        y, _ = ssd_reference(xs, dt, A, Bm, Cm, p["D"], chunk=cfg.ssm_chunk,
                             ctx=ctx)
    y = y.reshape(B_, L, di)
    y = rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    y = ctx.constrain(y, "batch", "seq", "act_ffn")
    return y @ p["out_proj"].astype(dt_)


# ---------------------------------------------------------------------------
# Decode: single-token state update
# ---------------------------------------------------------------------------
def mamba_init_cache(cfg: ArchConfig, batch: int, dtype=jnp.float32) -> dict:
    di, n = cfg.d_inner, cfg.ssm_state
    conv_dim = di + 2 * n
    return {
        "ssm": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim, n),
                         jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), dtype),
    }


def mamba_decode_step(p, x: jax.Array, cache: dict, cfg: ArchConfig,
                      ctx: ShardCtx):
    """x: (B, 1, D_model) -> (y (B,1,D), new cache)."""
    dt_ = x.dtype
    B_ = x.shape[0]
    di, n, h, pd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim

    zxbcdt = x[:, 0] @ p["in_proj"].astype(dt_)          # (B, ...)
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    # causal conv via rolling buffer
    W = cfg.ssm_conv_width
    hist = jnp.concatenate([cache["conv"], xBC[:, None]], axis=1)  # (B,W,C)
    w = p["conv_w"].astype(dt_)
    conv_out = jnp.einsum("bwc,wc->bc", hist, w) + p["conv_b"].astype(dt_)
    xBC = jax.nn.silu(conv_out)
    new_conv = hist[:, 1:]

    xs = xBC[..., :di].reshape(B_, h, pd).astype(jnp.float32)
    Bm = xBC[..., di:di + n].astype(jnp.float32)
    Cm = xBC[..., di + n:].astype(jnp.float32)
    dt = jax.nn.softplus(
        dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))  # (B,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    decay = jnp.exp(dt * A[None, :])                     # (B,H)
    state = cache["ssm"] * decay[..., None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dt, xs, Bm)
    y = jnp.einsum("bhpn,bn->bhp", state, Cm) \
        + xs * p["D"].astype(jnp.float32)[None, :, None]
    y = y.reshape(B_, di)
    y = rmsnorm(p["norm"], (y * jax.nn.silu(z.astype(jnp.float32))),
                cfg.norm_eps)
    y = (y.astype(dt_) @ p["out_proj"].astype(dt_))[:, None]
    return y, {"ssm": state, "conv": new_conv}
