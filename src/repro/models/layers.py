"""Shared neural-net layers (pure functional JAX)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distrib.logical import P, ShardCtx
from repro.kernels import twopart


# ---------------------------------------------------------------------------
# RMSNorm and LayerNorm
# ---------------------------------------------------------------------------
def rmsnorm_spec(d: int) -> dict:
    return {"scale": P((d,), ("embed",), init="ones")}


def rmsnorm(params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dt)


def layernorm(params, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


def norm_spec(cfg: ArchConfig, d: Optional[int] = None) -> dict:
    """The block norm's parameters: a scale, and a bias for LayerNorm."""
    d = d or cfg.d_model
    spec = rmsnorm_spec(d)
    if cfg.norm == "layer":
        spec["bias"] = P((d,), ("embed",), init="zeros")
    return spec


def norm(params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """The configuration's block norm (``cfg.norm``) at its epsilon."""
    if cfg.norm == "layer":
        return layernorm(params, x, cfg.norm_eps)
    return rmsnorm(params, x, cfg.norm_eps)


def linear(x: jax.Array, w: jax.Array) -> jax.Array:
    """x @ w in x's dtype; float32 ``x`` against narrower weights keeps
    float32 precision and reads ``w`` once (``twopart.matmul``)."""
    if twopart.wider(x.dtype, w.dtype):
        return twopart.matmul(x, w)
    return x @ w.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq   # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU) and plain GELU MLP
# ---------------------------------------------------------------------------
def mlp_spec(cfg: ArchConfig, d: Optional[int] = None, f: Optional[int] = None
             ) -> dict:
    d = d or cfg.d_model
    f = f or cfg.d_ff
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "wi": P((d, f), ("embed", "ffn")),
            "wg": P((d, f), ("embed", "ffn")),
            "wo": P((f, d), ("ffn", "embed")),
        }
    return {
        "wi": P((d, f), ("embed", "ffn")),
        "wo": P((f, d), ("ffn", "embed")),
    }


def mlp(params, x: jax.Array, cfg: ArchConfig, ctx: ShardCtx) -> jax.Array:
    dt = x.dtype
    if cfg.activation in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.activation == "swiglu" else (
            lambda a: jax.nn.gelu(a, approximate=True))
        h = act(x @ params["wg"].astype(dt)) * (x @ params["wi"].astype(dt))
    else:
        h = jax.nn.gelu(x @ params["wi"].astype(dt), approximate=True)
    h = ctx.constrain(h, "batch", "seq", "act_ffn")
    return h @ params["wo"].astype(dt)


# ---------------------------------------------------------------------------
# Embedding / unembedding with chunked cross-entropy
# ---------------------------------------------------------------------------
def embed_spec(cfg: ArchConfig) -> dict:
    spec = {"tok": P((cfg.vocab, cfg.d_model), ("vocab", "embed"))}
    if not cfg.tie_embeddings:
        spec["unembed"] = P((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    if cfg.lm_head_bias:
        spec["unembed_bias"] = P((cfg.vocab,), ("vocab",), init="zeros")
    return spec


def embed(params, tokens: jax.Array, dtype) -> jax.Array:
    return params["tok"].astype(dtype)[tokens]


def unembed_matrix(params, cfg: ArchConfig, dtype) -> jax.Array:
    if cfg.tie_embeddings:
        return params["tok"].astype(dtype).T
    return params["unembed"].astype(dtype)


def logits_last(params, cfg: ArchConfig, h_last: jax.Array) -> jax.Array:
    """(B, D) -> (B, V) logits for decode."""
    w = unembed_matrix(params, cfg, jnp.dtype(cfg.dtype))
    logits = linear(h_last, w).astype(jnp.float32)
    if cfg.lm_head_bias:
        logits = logits + params["unembed_bias"].astype(jnp.float32)
    return logits


def chunked_cross_entropy(
    params, cfg: ArchConfig, h: jax.Array, labels: jax.Array,
    ctx: ShardCtx, chunk: int = 1024,
):
    """Mean CE without materializing (B, S, V) logits.

    h: (B, S, D); labels: (B, S) int32, -1 = ignore.  Scans over sequence
    chunks; each chunk computes (B, chunk, V) logits, its log-softmax CE, and
    discards the logits.  f32 accumulation.
    """
    B, S, D = h.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n = S // chunk
    w = unembed_matrix(params, cfg, h.dtype)     # (D, V)
    bias = params["unembed_bias"].astype(jnp.float32) if cfg.lm_head_bias \
        else 0.0

    h_c = h.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    y_c = labels.reshape(B, n, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        loss_sum, count = carry
        hc, yc = xs
        logits = (hc @ w).astype(jnp.float32) + bias     # (B, chunk, V)
        logits = ctx.constrain(logits, "batch", "seq", "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(yc, 0)[..., None], axis=-1)[..., 0]
        valid = (yc >= 0)
        loss_sum = loss_sum + jnp.sum((lse - gold) * valid)
        count = count + jnp.sum(valid)
        return (loss_sum, count), None

    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (h_c, y_c))
    return loss_sum / jnp.maximum(count, 1).astype(jnp.float32)
