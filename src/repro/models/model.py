"""Model assembly: parameter specs, forward, train loss, prefill, decode.

Every architecture family is assembled as ``lax.scan`` over stacked per-layer
parameters (O(1)-in-depth HLO — essential for the 512-device dry-run compile
times), with per-layer boolean flags threaded through the scan for mixed
local/global attention patterns (gemma3) and grouped two-level scans for the
heterogeneous stacks (VLM cross-attention, zamba2 shared-attention hybrid).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distrib.logical import (
    P, ShardCtx, NOSHARD, abstract_params, init_params, spec_map)
from repro.models import attention as attn_mod
from repro.models import blocks as B
from repro.models import ssm as ssm_mod
from repro.models.blocks import ModelOpts
from repro.models.layers import (
    chunked_cross_entropy, embed, embed_spec, logits_last, norm, norm_spec,
    rmsnorm)


# ---------------------------------------------------------------------------
# Spec stacking helpers
# ---------------------------------------------------------------------------
def stack_spec(spec: dict, *ns: int) -> dict:
    """Prepend scan dims to every leaf (logical axis 'layers', never sharded)."""
    extra = tuple(ns)
    return spec_map(
        lambda p: P(extra + p.shape, ("layers",) * len(extra) + p.axes,
                    p.scale, p.init),
        spec)


def _groups(cfg: ArchConfig) -> Tuple[int, int, int]:
    """(n_groups, group_len, remainder) for grouped stacks."""
    if cfg.family == "hybrid":
        k = cfg.shared_attn_every
        return cfg.n_layers // k, k, cfg.n_layers % k
    if cfg.family == "vlm":
        k = cfg.cross_attn_every
        n = cfg.n_layers // k
        return n, k - 1, cfg.n_layers - n * k   # k-1 self + 1 cross per group
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # ---------------- parameter spec ----------------
    def param_spec(self) -> dict:
        cfg = self.cfg
        spec: Dict[str, Any] = {"embed": embed_spec(cfg),
                                "ln_f": norm_spec(cfg)}
        if cfg.family == "audio":
            spec["frame_proj"] = P((cfg.frame_dim, cfg.d_model),
                                   (None, "embed"))
        if cfg.family in ("dense", "moe", "audio"):
            spec["layers"] = stack_spec(B.dense_block_spec(cfg), cfg.n_layers)
        elif cfg.family == "ssm":
            spec["layers"] = stack_spec(B.mamba_block_spec(cfg), cfg.n_layers)
        elif cfg.family == "hybrid":
            g, k, r = _groups(cfg)
            spec["groups"] = stack_spec(B.mamba_block_spec(cfg), g, k)
            spec["shared"] = B.dense_block_spec(cfg)
            if r:
                spec["rem"] = stack_spec(B.mamba_block_spec(cfg), r)
        elif cfg.family == "vlm":
            g, k, _ = _groups(cfg)
            spec["self"] = stack_spec(B.dense_block_spec(cfg), g, k)
            spec["cross"] = stack_spec(B.cross_block_spec(cfg), g)
        else:
            raise ValueError(cfg.family)
        return spec

    def init(self, rng: jax.Array, dtype=jnp.float32):
        return init_params(rng, self.param_spec(), dtype)

    def abstract_params(self, dtype=jnp.float32):
        return abstract_params(self.param_spec(), dtype)

    def global_flags(self) -> np.ndarray:
        return np.array([g for _, g in self.cfg.layer_pattern()], bool)

    # ---------------- forward ----------------
    def _embed_in(self, params, batch, dtype):
        cfg = self.cfg
        if cfg.family == "audio":
            return batch["frames"].astype(dtype) @ params["frame_proj"].astype(
                dtype)
        return embed(params["embed"], batch["tokens"], dtype)

    def forward(self, params, batch, ctx: ShardCtx = NOSHARD,
                opts: ModelOpts = ModelOpts()) -> Tuple[jax.Array, jax.Array]:
        """-> (hidden (B,S,D) after final norm, aux loss)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        params = _precast(params, dtype, self.param_spec(), ctx)
        h = self._embed_in(params, batch, dtype).astype(cfg.activation_dtype)
        h = ctx.constrain(h, "batch", "seq", "act_embed")
        S = h.shape[1]
        positions = jnp.arange(S)[None]
        aux = jnp.zeros((), jnp.float32)

        if cfg.family in ("dense", "moe", "audio"):
            if opts.banded_local and cfg.local_global_ratio \
                    and cfg.sliding_window:
                # superblock restructuring: local layers take the BANDED
                # attention path (only the reachable KV band is computed —
                # no masked-out work), global layers stay full-causal.
                h, aux2 = self._forward_banded(params, h, cfg, ctx, opts,
                                               positions)
                aux = aux + aux2
            else:
                flags = jnp.asarray(self.global_flags())

                def body(hh, xs):
                    p_i, flag = xs
                    return B.dense_block(p_i, hh, cfg, ctx, opts,
                                         positions=positions, is_global=flag)

                h, auxs = jax.lax.scan(B.remat_wrap(body, opts), h,
                                       (params["layers"], flags))
                aux = aux + auxs.sum()

        elif cfg.family == "ssm":
            def body(hh, p_i):
                return B.mamba_block(p_i, hh, cfg, ctx, opts), None

            h, _ = jax.lax.scan(B.remat_wrap(body, opts), h,
                                params["layers"])

        elif cfg.family == "hybrid":
            shared = params["shared"]

            def inner(hh, p_i):
                return B.mamba_block(p_i, hh, cfg, ctx, opts), None

            def group(hh, p_g):
                hh, _ = jax.lax.scan(inner, hh, p_g)
                hh, _ = B.dense_block(shared, hh, cfg, ctx, opts,
                                      positions=positions)
                return hh, None

            h, _ = jax.lax.scan(B.remat_wrap(group, opts), h,
                                params["groups"])
            if "rem" in params:
                h, _ = jax.lax.scan(B.remat_wrap(inner, opts), h,
                                    params["rem"])

        elif cfg.family == "vlm":
            img = batch["image_embeds"].astype(dtype)

            def inner(hh, p_i):
                hh, _ = B.dense_block(p_i, hh, cfg, ctx, opts,
                                      positions=positions)
                return hh, None

            def group(hh, xs):
                p_self, p_cross = xs
                hh, _ = jax.lax.scan(inner, hh, p_self)
                hh = B.cross_block(p_cross, hh, img, cfg, ctx, opts)
                return hh, None

            h, _ = jax.lax.scan(B.remat_wrap(group, opts), h,
                                (params["self"], params["cross"]))
        else:
            raise ValueError(cfg.family)

        return norm(params["ln_f"], h, cfg), aux

    def _forward_banded(self, params, h, cfg, ctx, opts, positions):
        """Local:global superblock scan (e.g. gemma3's 5:1 pattern).

        The stacked 62-layer params are statically regrouped into
        (n_groups, ratio) local stacks + (n_groups,) global stacks + a
        local remainder, so the structurally different banded attention
        can be scanned without per-layer branching.
        """
        r = cfg.local_global_ratio + 1
        n_groups = cfg.n_layers // r
        li = np.array([[g * r + j for j in range(r - 1)]
                       for g in range(n_groups)])
        gi = np.array([g * r + (r - 1) for g in range(n_groups)])
        rem = np.arange(n_groups * r, cfg.n_layers)

        take = lambda idx: jax.tree.map(lambda x: x[idx], params["layers"])
        p_loc, p_glob = take(li), take(gi)

        def local_body(hh, p_i):
            hh, a = B.dense_block(p_i, hh, cfg, ctx, opts,
                                  positions=positions, banded=True)
            return hh, a

        def group(hh, xs):
            pl, pg = xs
            hh, a1 = jax.lax.scan(local_body, hh, pl)
            hh, a2 = B.dense_block(pg, hh, cfg, ctx, opts,
                                   positions=positions, is_global=True)
            return hh, a1.sum() + a2

        h, auxs = jax.lax.scan(B.remat_wrap(group, opts), h,
                               (p_loc, p_glob))
        aux = auxs.sum()
        if len(rem):
            h, auxs2 = jax.lax.scan(B.remat_wrap(local_body, opts), h,
                                    take(rem))
            aux = aux + auxs2.sum()
        return h, aux

    # ---------------- training loss ----------------
    def loss(self, params, batch, ctx: ShardCtx = NOSHARD,
             opts: ModelOpts = ModelOpts()) -> jax.Array:
        h, aux = self.forward(params, batch, ctx, opts)
        ce = chunked_cross_entropy(
            params["embed"], self.cfg, h, batch["labels"], ctx,
            chunk=opts.ce_chunk)
        return ce + opts.aux_loss_coef * aux

    # ---------------- prefill (forward + KV/state cache) ----------------
    def prefill(self, params, batch, ctx: ShardCtx = NOSHARD,
                opts: ModelOpts = ModelOpts()):
        """-> (last-position logits (B, V) f32, cache)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        params = _precast(params, dtype, self.param_spec(), ctx)
        h = self._embed_in(params, batch, dtype).astype(cfg.activation_dtype)
        h = ctx.constrain(h, "batch", "seq", "act_embed")
        S = h.shape[1]
        positions = jnp.arange(S)[None]
        cache: Dict[str, Any] = {}

        if cfg.family in ("dense", "moe"):
            flags = jnp.asarray(self.global_flags())

            def body(hh, xs):
                p_i, flag = xs
                hh2, kv = _dense_prefill(p_i, hh, cfg, ctx, opts,
                                         positions, flag)
                return hh2, kv

            h, (ks, vs) = jax.lax.scan(body, h, (params["layers"], flags))
            cache = {"k": ks, "v": vs}

        elif cfg.family == "ssm":
            def body(hh, p_i):
                return _mamba_prefill(p_i, hh, cfg, ctx, opts)

            h, (ssm, conv) = jax.lax.scan(body, h, params["layers"])
            cache = {"ssm": ssm, "conv": conv}

        elif cfg.family == "hybrid":
            shared = params["shared"]

            def inner(hh, p_i):
                return _mamba_prefill(p_i, hh, cfg, ctx, opts)

            def group(hh, p_g):
                hh, (ssm, conv) = jax.lax.scan(inner, hh, p_g)
                hh, kv = _dense_prefill(shared, hh, cfg, ctx, opts,
                                        positions, True)
                return hh, (ssm, conv, kv[0], kv[1])

            h, (ssm, conv, ks, vs) = jax.lax.scan(group, h, params["groups"])
            cache = {"ssm": ssm, "conv": conv, "k": ks, "v": vs}
            if "rem" in params:
                h, (rssm, rconv) = jax.lax.scan(inner, h, params["rem"])
                cache["rem_ssm"], cache["rem_conv"] = rssm, rconv

        elif cfg.family == "vlm":
            img = batch["image_embeds"].astype(dtype)

            def inner(hh, p_i):
                hh2, kv = _dense_prefill(p_i, hh, cfg, ctx, opts,
                                         positions, True)
                return hh2, kv

            def group(hh, xs):
                p_self, p_cross = xs
                hh, kv = jax.lax.scan(inner, hh, p_self)
                xk, xv = attn_mod.project_kv(p_cross["xattn"], img, cfg)
                hh = B.cross_block_cached(p_cross, hh, xk, xv, cfg, ctx)
                return hh, (kv[0], kv[1], xk, xv)

            h, (ks, vs, xks, xvs) = jax.lax.scan(
                group, h, (params["self"], params["cross"]))
            cache = {"k": ks, "v": vs, "xk": xks, "xv": xvs}

        elif cfg.family == "audio":
            # encoder-only: "prefill" = full inference, logits per frame
            h, _ = self.forward(params, batch, ctx, opts)
            w = params["embed"]["tok"].astype(h.dtype).T if cfg.tie_embeddings \
                else params["embed"]["unembed"].astype(h.dtype)
            return (h @ w).astype(jnp.float32), {}
        else:
            raise ValueError(cfg.family)

        h = norm(params["ln_f"], h, cfg)
        return logits_last(params["embed"], cfg, h[:, -1]), cache

    # ---------------- decode ----------------
    def init_cache(self, batch: int, seq: int, dtype=jnp.bfloat16) -> dict:
        cfg = self.cfg
        Hkv, Dh = cfg.n_kv_heads, cfg.head_dim

        def kv(*lead):        # head-major: the order decode_attention reads
            return jnp.zeros(lead + (batch, Hkv, seq, Dh), dtype)

        if cfg.family in ("dense", "moe"):
            return {"k": kv(cfg.n_layers), "v": kv(cfg.n_layers)}
        if cfg.family == "ssm":
            m = ssm_mod.mamba_init_cache(cfg, batch, dtype)
            return {"ssm": _tile(m["ssm"], cfg.n_layers),
                    "conv": _tile(m["conv"], cfg.n_layers)}
        if cfg.family == "hybrid":
            g, k, r = _groups(cfg)
            m = ssm_mod.mamba_init_cache(cfg, batch, dtype)
            cache = {
                "ssm": _tile(_tile(m["ssm"], k), g),
                "conv": _tile(_tile(m["conv"], k), g),
                "k": kv(g), "v": kv(g),
            }
            if r:
                cache["rem_ssm"] = _tile(m["ssm"], r)
                cache["rem_conv"] = _tile(m["conv"], r)
            return cache
        if cfg.family == "vlm":
            g, k, _ = _groups(cfg)
            return {
                "k": kv(g, k), "v": kv(g, k),
                "xk": jnp.zeros((g, batch, cfg.n_image_tokens, Hkv, Dh),
                                dtype),
                "xv": jnp.zeros((g, batch, cfg.n_image_tokens, Hkv, Dh),
                                dtype),
            }
        raise ValueError(f"{cfg.family} has no decode cache")

    def decode_step(self, params, batch, cache, ctx: ShardCtx = NOSHARD,
                    opts: ModelOpts = ModelOpts(), with_stats: bool = False):
        """One token for every sequence in the batch.

        batch: {"token": (B,1) int32, "pos": scalar int32 or (B,) int32}
        -> (logits (B,V) f32, new cache), and with ``with_stats`` a dict of
        per-step counts: for an MoE model ``held``, ``(B, L)`` int32, bit
        ``e`` set where the slot's token was routed to held expert ``e`` in
        that layer (dense/moe families); empty otherwise.

        A scalar ``pos`` is the lockstep path (every sequence at the same
        position); a ``(B,)`` vector gives each slot its own position —
        rope, attention masking, and the KV-cache write all happen at the
        slot's own occupancy (continuous batching).  Per-slot positions are
        supported for the dense/moe (KV cache) and ssm (position-free
        recurrent state) families.

        K/V caches are head-major, ``(..., B, Hkv, S, D)`` (``init_cache``),
        and are read and written where they lie: the whole stacks ride in
        the layer scan's carry (for hybrid, the group scan's; for vlm, both
        scans'), each layer writes its slots' rows in place
        (``attention.write_rows``) and then attention reads its slab at the
        layer's index.  An MoE layer is the dropless held-experts layer
        (``moe.held_experts_ffn``); its expert stacks stay out of the layer
        scan's sliced inputs and each layer reads its own at its index.
        ``opts.use_kernel`` picks the Pallas kernels (``decode_attention``,
        ``expert_ffn``) over plain jnp at those two points of the dense/moe
        families; nothing else differs.

        Traced under the name scope ``decode_step``, so that its operations
        carry that name in a profile.
        """
        with jax.named_scope("decode_step"):
            logits, cache, stats = self._decode_step(params, batch, cache,
                                                     ctx, opts)
        return (logits, cache, stats) if with_stats else (logits, cache)

    def _decode_step(self, params, batch, cache, ctx: ShardCtx,
                     opts: ModelOpts):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        params = _precast(params, dtype, self.param_spec(), ctx)
        pos = batch["pos"]
        per_slot = jnp.ndim(pos) == 1
        h = embed(params["embed"], batch["token"], dtype)   # (B,1,D)
        h = h.astype(cfg.activation_dtype)
        h = ctx.constrain(h, "batch", "seq", "act_embed")

        stats: Dict[str, Any] = {}
        if cfg.family in ("dense", "moe"):
            layers, experts = params["layers"], None
            if cfg.n_experts:
                moe = layers["moe"]
                experts = {k: moe[k] for k in ("wg", "wi", "wo")}
                layers = dict(layers, moe={"router": moe["router"]})

            def body(carry, xs):
                hh, kc, vc = carry
                p_i, flag, layer = xs
                hh, kc, vc, held = B.dense_block_decode(
                    p_i, hh, kc, vc, cfg, ctx, pos=pos, at=(layer,),
                    is_global=flag, use_kernel=opts.use_kernel,
                    experts=experts)
                return (hh, kc, vc), held

            (h, kc, vc), held = jax.lax.scan(
                body, (h, cache["k"], cache["v"]),
                (layers, jnp.asarray(self.global_flags()),
                 jnp.arange(cfg.n_layers, dtype=jnp.int32)))
            cache = {"k": kc, "v": vc}

        elif cfg.family == "ssm":
            def body(hh, xs):
                p_i, c = xs
                hh, c = B.mamba_block_decode(p_i, hh, c, cfg, ctx)
                return hh, c

            h, new = jax.lax.scan(
                body, h, (params["layers"],
                          {"ssm": cache["ssm"], "conv": cache["conv"]}))
            cache = {"ssm": new["ssm"], "conv": new["conv"]}

        elif cfg.family == "hybrid":
            if per_slot:
                raise NotImplementedError(
                    "per-slot decode positions: hybrid family serves via "
                    "the lockstep path")
            shared = params["shared"]
            g, _, _ = _groups(cfg)

            def inner(hh, xs):
                p_i, c = xs
                hh, c = B.mamba_block_decode(p_i, hh, c, cfg, ctx)
                return hh, c

            def group(carry, xs):
                hh, kc, vc = carry
                p_g, cg, gi = xs
                hh, cg = jax.lax.scan(inner, hh, (p_g, cg))
                hh, kc, vc, _ = B.dense_block_decode(
                    shared, hh, kc, vc, cfg, ctx, pos=pos, at=(gi,))
                return (hh, kc, vc), cg

            (h, kc, vc), cg = jax.lax.scan(
                group, (h, cache["k"], cache["v"]),
                (params["groups"],
                 {"ssm": cache["ssm"], "conv": cache["conv"]},
                 jnp.arange(g, dtype=jnp.int32)))
            new = {"ssm": cg["ssm"], "conv": cg["conv"], "k": kc, "v": vc}
            if "rem" in params:
                h, rc = jax.lax.scan(
                    inner, h,
                    (params["rem"], {"ssm": cache["rem_ssm"],
                                     "conv": cache["rem_conv"]}))
                new["rem_ssm"], new["rem_conv"] = rc["ssm"], rc["conv"]
            cache = new

        elif cfg.family == "vlm":
            if per_slot:
                raise NotImplementedError(
                    "per-slot decode positions: vlm family serves via "
                    "the lockstep path")
            g, k, _ = _groups(cfg)

            def group(carry, xs):
                p_self, p_cross, xk, xv, gi = xs

                def inner(carry, xs):
                    hh, kc, vc = carry
                    p_i, j = xs
                    hh, kc, vc, _ = B.dense_block_decode(
                        p_i, hh, kc, vc, cfg, ctx, pos=pos, at=(gi, j))
                    return (hh, kc, vc), None

                (hh, kc, vc), _ = jax.lax.scan(
                    inner, carry, (p_self, jnp.arange(k, dtype=jnp.int32)))
                hh = B.cross_block_cached(p_cross, hh, xk, xv, cfg, ctx)
                return (hh, kc, vc), None

            (h, kc, vc), _ = jax.lax.scan(
                group, (h, cache["k"], cache["v"]),
                (params["self"], params["cross"], cache["xk"], cache["xv"],
                 jnp.arange(g, dtype=jnp.int32)))
            cache = {"k": kc, "v": vc, "xk": cache["xk"], "xv": cache["xv"]}
        else:
            raise ValueError(f"{cfg.family} has no decode step")

        if cfg.family == "moe":
            stats["held"] = held[..., 0].T                    # (B, L)
        h = norm(params["ln_f"], h, cfg)
        return logits_last(params["embed"], cfg, h[:, 0]), cache, stats


# ---------------------------------------------------------------------------
# Prefill block variants (return the projected K/V so the cache can be built)
# ---------------------------------------------------------------------------
def _dense_prefill(p, h, cfg, ctx, opts, positions, is_global):
    hn = norm(p["ln1"], h, cfg)
    q = attn_mod.project_q(p["attn"], hn, cfg)
    k, v = attn_mod.project_kv(p["attn"], hn, cfg)
    q = attn_mod.rope(q, positions, cfg.rope_theta)
    k = attn_mod.rope(k, positions, cfg.rope_theta)
    o = attn_mod.chunked_mha(
        q, k, v, ctx, causal=cfg.causal, is_global=is_global,
        window=cfg.sliding_window, chunk=opts.attn_chunk)
    h = h + attn_mod.out_proj(p["attn"], o, cfg)
    hn = norm(p["ln2"], h, cfg)
    if cfg.n_experts:            # dropless, as the decode step serves it
        from repro.models import moe as moe_mod
        f, _ = moe_mod.held_experts_ffn(p["moe"], hn, cfg)
    else:
        from repro.models.layers import mlp
        f = mlp(p["mlp"], hn, cfg, ctx)
    # head-major (B,Hkv,S,D), the decode cache's order
    return h + f, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))


def _mamba_prefill(p, h, cfg, ctx, opts):
    """Mamba block returning (h, (final ssm state, conv tail))."""
    dt_ = h.dtype
    B_, L, _ = h.shape
    di, n = cfg.d_inner, cfg.ssm_state
    hn = rmsnorm(p["ln"], h, cfg.norm_eps)
    zxbcdt = hn @ p["mixer"]["in_proj"].astype(dt_)
    z, xBC, dt = ssm_mod._split_proj(cfg, zxbcdt)
    xBC_conv = ctx.constrain(
        ssm_mod._causal_conv(xBC, p["mixer"]["conv_w"],
                             p["mixer"]["conv_b"]),
        "batch", "seq", "inner")
    xs = xBC_conv[..., :di].reshape(B_, L, cfg.ssm_heads, cfg.ssm_head_dim)
    Bm = xBC_conv[..., di:di + n]
    Cm = xBC_conv[..., di + n:]
    dtv = jax.nn.softplus(
        dt.astype(jnp.float32) + p["mixer"]["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["mixer"]["A_log"].astype(jnp.float32))
    y, state = ssm_mod.ssd_reference(xs, dtv, A, Bm, Cm, p["mixer"]["D"],
                                     chunk=cfg.ssm_chunk, ctx=ctx)
    y = y.reshape(B_, L, di)
    y = rmsnorm(p["mixer"]["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    h = h + y @ p["mixer"]["out_proj"].astype(dt_)
    conv_tail = xBC[:, L - (cfg.ssm_conv_width - 1):, :]   # pre-activation
    return h, (state, conv_tail.astype(dt_))


def _tile(x: jax.Array, n: int) -> jax.Array:
    return jnp.tile(x[None], (n,) + (1,) * x.ndim)


def _precast(params, dtype, spec=None, ctx: ShardCtx = NOSHARD):
    """Cast the whole (f32 master) parameter tree to the compute dtype ONCE,
    before any layer scan: FSDP all-gathers then move bf16 instead of f32
    (halves weight-gather traffic) and the per-layer ``astype`` calls become
    no-ops.  Differentiable — gradients flow back to the f32 masters.

    When the parameter spec is available the cast copies carry the SAME
    sharding constraints as the masters — without this, SPMD may materialize
    the bf16 copies replicated (observed: 56 GB/chip on the MoE expert
    stacks)."""
    if dtype == jnp.float32:
        return params

    def walk(sp, pr):
        if isinstance(pr, dict):
            return {k: walk(sp[k] if sp else None, v)
                    for k, v in pr.items()}
        if hasattr(pr, "ndim") and pr.ndim >= 2 and pr.dtype == jnp.float32:
            x = pr.astype(dtype)
            if sp is not None:
                x = ctx.constrain(x, *sp.axes)
            return x
        return pr

    return walk(spec, params)


# ---------------------------------------------------------------------------
# Logical axes for decode caches (mirrors Model.init_cache structure).
# "kv_heads" and "kv_hd" both map to "model"; the divisibility guard in
# logical_to_spec picks whichever evenly divides (GQA kv=8 on a 16-way model
# axis falls through to sharding head_dim — a flash-decode-style partial-K
# layout).  "kv_seq" maps to "data" only in the single-sequence long-context
# strategy (see repro.launch.steps).
# ---------------------------------------------------------------------------
KV_AXES = ("layers", "batch", "kv_heads", "kv_seq", "kv_hd")
SSM_AXES = ("layers", "batch", "ssm_heads", None, "state")
CONV_AXES = ("layers", "batch", None, "inner")


def cache_axes(cfg: ArchConfig) -> dict:
    if cfg.family in ("dense", "moe"):
        return {"k": KV_AXES, "v": KV_AXES}
    if cfg.family == "ssm":
        return {"ssm": SSM_AXES, "conv": CONV_AXES}
    if cfg.family == "hybrid":
        g, k, r = _groups(cfg)
        ax = {
            "ssm": ("layers",) + SSM_AXES, "conv": ("layers",) + CONV_AXES,
            "k": KV_AXES, "v": KV_AXES,
        }
        if r:
            ax["rem_ssm"], ax["rem_conv"] = SSM_AXES, CONV_AXES
        return ax
    if cfg.family == "vlm":
        img_axes = ("layers", "batch", "img", "kv_heads", "kv_hd")
        return {"k": ("layers",) + KV_AXES, "v": ("layers",) + KV_AXES,
                "xk": img_axes, "xv": img_axes}
    raise ValueError(f"{cfg.family} has no decode cache")


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
