"""Attention: GQA/MQA/MHA, causal / bidirectional / sliding-window / cross.

Reference implementations are *chunked* over the query dimension (never
materializing the full (S, S) score matrix) so that long-context shapes fit
the per-chip memory envelope; the Pallas flash kernels in ``repro.kernels``
are the TPU-optimized equivalents and are validated against these.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distrib.logical import P, ShardCtx
from repro.kernels import twopart
from repro.models.layers import linear, rope

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------
def attn_spec(cfg: ArchConfig, cross: bool = False) -> dict:
    d, qd, kd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    spec = {
        "wq": P((d, qd), ("embed", "q_heads")),
        "wk": P((d, kd), ("embed", "kv_heads")),
        "wv": P((d, kd), ("embed", "kv_heads")),
        "wo": P((qd, d), ("q_heads", "embed")),
    }
    if cfg.qkv_bias and not cross:
        spec["bq"] = P((qd,), ("q_heads",), init="zeros")
        spec["bk"] = P((kd,), ("kv_heads",), init="zeros")
        spec["bv"] = P((kd,), ("kv_heads",), init="zeros")
    if cfg.attn_out_bias and not cross:
        spec["bo"] = P((d,), ("embed",), init="zeros")
    return spec


def project_q(p, x, cfg: ArchConfig):
    dt = x.dtype
    q = linear(x, p["wq"])
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    B, S = x.shape[:2]
    return q.reshape(B, S, cfg.n_heads, cfg.head_dim)


def project_kv(p, x, cfg: ArchConfig):
    dt = x.dtype
    k = linear(x, p["wk"])
    v = linear(x, p["wv"])
    if "bk" in p:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    B, S = x.shape[:2]
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    return k, v


def out_proj(p, o, cfg: ArchConfig):
    B, S = o.shape[:2]
    out = linear(o.reshape(B, S, cfg.q_dim), p["wo"])
    if "bo" in p:
        out = out + p["bo"].astype(o.dtype)
    return out


# ---------------------------------------------------------------------------
# Masked scores helper
# ---------------------------------------------------------------------------
def _mask(qpos, kpos, *, causal, is_global, window):
    """(Sq, Sk) boolean allowed-mask.

    ``is_global`` may be a traced scalar bool (scan-over-layers with mixed
    local/global patterns): allowed = causal & (global | within window).
    """
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m = kpos[None, :] <= qpos[:, None]
    if window:
        in_win = kpos[None, :] > (qpos[:, None] - window)
        m = m & (in_win | is_global)
    return m


# ---------------------------------------------------------------------------
# Chunked multi-head attention (full keys per query chunk)
# ---------------------------------------------------------------------------
def chunked_mha(
    q: jax.Array, k: jax.Array, v: jax.Array, ctx: ShardCtx, *,
    causal: bool = True,
    is_global=True,
    window: int = 0,
    q_offset: int = 0,
    chunk: int = 1024,
) -> jax.Array:
    """q: (B,Sq,Hq,D); k,v: (B,Sk,Hkv,D) -> (B,Sq,Hq,D)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    chunk = min(chunk, Sq)
    assert Sq % chunk == 0, (Sq, chunk)
    n = Sq // chunk
    kpos = jnp.arange(Sk)

    qg = q.reshape(B, Sq, Hkv, G, D)

    def block(qc: jax.Array, start) -> jax.Array:
        qpos = q_offset + start + jnp.arange(chunk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qc, k,
                       preferred_element_type=jnp.float32) * scale
        m = _mask(qpos, kpos, causal=causal, is_global=is_global,
                  window=window)
        s = jnp.where(m[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v)

    if n == 1:
        o = block(qg, 0)
    else:
        def body(_, xs):
            qc, start = xs
            return None, block(qc, start)

        qs = qg.reshape(B, n, chunk, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
        starts = jnp.arange(n) * chunk
        # flash-style: backward recomputes per-chunk scores (never stores
        # the full (Sq, Sk) softmax across chunks)
        _, os = jax.lax.scan(jax.checkpoint(body), None, (qs, starts))
        o = os.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hkv, G, D)
    o = o.reshape(B, Sq, Hq, D)
    return ctx.constrain(o, "batch", "seq", "act_heads", None)


# ---------------------------------------------------------------------------
# Banded (sliding-window-limited) attention — beyond-paper optimization.
# Only the KV band that the window can reach is sliced per query chunk, so
# masked-out compute is never issued.  Used when the whole stack segment is
# local (see the gemma3 superblock restructuring in EXPERIMENTS.md §Perf).
# ---------------------------------------------------------------------------
def banded_mha(
    q: jax.Array, k: jax.Array, v: jax.Array, ctx: ShardCtx, *,
    window: int, q_offset: int = 0, chunk: int = 512,
) -> jax.Array:
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    chunk = min(chunk, Sq)
    assert Sq % chunk == 0
    n = Sq // chunk
    band = min(Sk, _round_up(window + chunk, chunk))

    qg = q.reshape(B, Sq, Hkv, G, D)

    def block(qc, start):
        # start is the first query position of this chunk (traced).
        qpos = q_offset + start + jnp.arange(chunk)
        k0 = jnp.clip(q_offset + start + chunk - band, 0, Sk - band)
        kc = jax.lax.dynamic_slice_in_dim(k, k0, band, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, k0, band, axis=1)
        kpos = k0 + jnp.arange(band)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        causal = kpos[None, :] <= qpos[:, None]
        in_win = kpos[None, :] > (qpos[:, None] - window)
        s = jnp.where((causal & in_win)[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, vc)

    if n == 1:
        o = block(qg, 0)
    else:
        def body(_, xs):
            qc, start = xs
            return None, block(qc, start)

        qs = qg.reshape(B, n, chunk, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
        _, os = jax.lax.scan(jax.checkpoint(body), None,
                             (qs, jnp.arange(n) * chunk))
        o = os.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hkv, G, D)
    o = o.reshape(B, Sq, Hq, D)
    return ctx.constrain(o, "batch", "seq", "act_heads", None)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Single-token decode attention against a KV cache
# ---------------------------------------------------------------------------
def decode_mha(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, ctx: ShardCtx, *,
    pos, is_global=True, window: int = 0,
) -> jax.Array:
    """q: (B,1,Hq,D); caches: (B,Hkv,Sk,D); attends positions <= ``pos``,
    the current token's included.

    ``pos`` is either a scalar (lockstep batch: every sequence sits at the
    same position) or a ``(B,)`` vector of per-slot positions (continuous
    batching: each slot has its own occupancy).  The scalar case lowers to
    a single broadcast mask row.
    """
    B, _, Hq, D = q.shape
    _, Hkv, Sk, _ = k_cache.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(Sk)
    posb = jnp.reshape(jnp.asarray(pos), (-1, 1))    # (1,1) | (B,1)
    m = kpos[None, :] <= posb
    if window:
        m = m & ((kpos[None, :] > posb - window) | is_global)
    s = jnp.where(m[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    o = jnp.einsum("bkgs,bksd->bkgd", p, v_cache)
    return o.reshape(B, 1, Hq, D)


def write_rows(cache: jax.Array, rows: jax.Array, pos, *, at=()):
    """Write one K or V row per slot into a head-major cache, in place.

    cache: (*lead, B, Hkv, S, D); rows: (*lead[len(at):], B, Hkv, 1, D),
    written at the leading indices ``at`` (e.g. the layer) and at position
    ``pos`` — a scalar (lockstep: one update for the whole batch) or ``(B,)``
    per-slot positions (one update per slot, unrolled).  Every update is a
    ``dynamic_update_slice`` of the cache itself, which XLA performs in the
    cache's buffer: nothing of the cache is copied or relaid out.
    """
    nb = cache.ndim - 4                       # index of the batch axis
    rows = rows.astype(cache.dtype).reshape((1,) * len(at) + rows.shape)
    at = tuple(jnp.asarray(a, jnp.int32) for a in at)
    zero = jnp.zeros((), jnp.int32)
    lead = at + (zero,) * (nb - len(at))
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice(
            cache, rows, lead + (zero, zero, pos, zero))
    for b in range(cache.shape[nb]):
        cache = jax.lax.dynamic_update_slice(
            cache, rows[..., b:b + 1, :, :, :],
            lead + (jnp.int32(b), zero, pos[b], zero))
    return cache


# ---------------------------------------------------------------------------
# Full self-attention layer wrappers
# ---------------------------------------------------------------------------
def self_attention(
    p, x: jax.Array, cfg: ArchConfig, ctx: ShardCtx, *,
    positions: jax.Array, is_global=True, chunk: int = 1024,
    banded: bool = False,
) -> jax.Array:
    q = project_q(p, x, cfg)
    k, v = project_kv(p, x, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if banded and cfg.sliding_window:
        o = banded_mha(q, k, v, ctx, window=cfg.sliding_window, chunk=chunk)
    else:
        o = chunked_mha(
            q, k, v, ctx, causal=cfg.causal, is_global=is_global,
            window=cfg.sliding_window, chunk=chunk)
    return out_proj(p, o, cfg)


def cross_attention(
    p, x: jax.Array, kv_src: jax.Array, cfg: ArchConfig, ctx: ShardCtx, *,
    chunk: int = 1024,
) -> jax.Array:
    """x attends to kv_src (e.g. image-patch embeddings); no mask, no RoPE."""
    q = project_q(p, x, cfg)
    k, v = project_kv(p, kv_src, cfg)
    o = chunked_mha(q, k, v, ctx, causal=False, chunk=chunk)
    return out_proj(p, o, cfg)


def refuse_windowed_kernel(cfg: ArchConfig) -> None:
    """Raise ``ValueError`` for a config with a sliding window: the
    ``decode_attention`` kernel has no window mask."""
    if cfg.sliding_window:
        raise ValueError(
            f"{cfg.name}: the decode_attention kernel has no sliding window "
            "mask; decode windowed configs with use_kernel=False")


def decode_self_attention(
    p, x: jax.Array, k_cache, v_cache, cfg: ArchConfig, ctx: ShardCtx, *,
    pos, at, is_global=True, use_kernel: bool = False,
):
    """One-token decode step against head-major KV-cache stacks.

    ``k_cache``/``v_cache`` are the whole ``(*lead, B, Hkv, S, D)`` stacks
    that ride in the layer scan's carry, and ``at`` is this layer's leading
    indices into them.  ``pos`` is a scalar (lockstep) or ``(B,)`` per-slot
    positions (continuous batching); rope is applied at each slot's own
    position.  Each slot's new K/V row is written in place at its position
    (``write_rows``), then attention reads the layer's slab, the new token
    included: with ``use_kernel`` the flash-decode Pallas kernel
    (``repro.kernels.ops.decode_attention``) reads it straight out of the
    stack with per-slot ``length = pos + 1`` (stacks with one leading axis,
    no sliding window); otherwise ``decode_mha`` attends the slab's
    positions ``<= pos``.  Returns ``(out, k_cache, v_cache)``, the stacks
    with the rows written.
    """
    B = x.shape[0]
    q = project_q(p, x, cfg)                       # (B,1,Hq,D)
    k_new, v_new = project_kv(p, x, cfg)           # (B,1,Hkv,D)
    posv = jnp.broadcast_to(jnp.reshape(jnp.asarray(pos), (-1, 1)), (B, 1))
    q = rope(q, posv, cfg.rope_theta)
    k_new = rope(k_new, posv, cfg.rope_theta).transpose(0, 2, 1, 3)
    v_new = v_new.transpose(0, 2, 1, 3)            # (B,Hkv,1,D)
    k_cache = write_rows(k_cache, k_new, pos, at=at)
    v_cache = write_rows(v_cache, v_new, pos, at=at)
    if use_kernel:
        refuse_windowed_kernel(cfg)
        from repro.kernels import ops as kernel_ops
        (layer,) = at
        o = kernel_ops.decode_attention(
            q.astype(k_cache.dtype).reshape(B, cfg.n_heads, cfg.head_dim),
            k_cache, v_cache, posv[:, 0].astype(jnp.int32) + 1, layer,
            precise=twopart.wider(x.dtype, cfg.dtype))
        o = o.reshape(B, 1, cfg.n_heads, cfg.head_dim)
    else:
        o = decode_mha(q, k_cache[at], v_cache[at], ctx, pos=pos,
                       is_global=is_global, window=cfg.sliding_window)
    return out_proj(p, o.astype(x.dtype), cfg), k_cache, v_cache
