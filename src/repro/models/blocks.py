"""Transformer / MoE / Mamba layer blocks (pre-norm residual)."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distrib.logical import ShardCtx
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (mlp, mlp_spec, norm, norm_spec, rmsnorm,
                                 rmsnorm_spec)


@dataclasses.dataclass(frozen=True)
class ModelOpts:
    """Run-time knobs — the inner configuration space of the autotuner."""
    attn_chunk: int = 512
    ce_chunk: int = 1024
    remat: str = "full"          # none | full | dots
    banded_local: bool = False   # banded sliding-window attention path
    use_kernel: bool = False     # Pallas kernels (TPU target)
    aux_loss_coef: float = 0.01


def remat_wrap(fn, opts: ModelOpts):
    if opts.remat == "none":
        return fn
    if opts.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Dense / MoE attention block
# ---------------------------------------------------------------------------
def dense_block_spec(cfg: ArchConfig) -> dict:
    spec = {
        "ln1": norm_spec(cfg),
        "attn": attn.attn_spec(cfg),
        "ln2": norm_spec(cfg),
    }
    if cfg.n_experts:
        spec["moe"] = moe_mod.moe_spec(cfg)
    else:
        spec["mlp"] = mlp_spec(cfg)
    return spec


def dense_block(p, h, cfg: ArchConfig, ctx: ShardCtx, opts: ModelOpts, *,
                positions, is_global=True, banded=False):
    """Returns (h, aux_loss)."""
    h = ctx.constrain(h, "batch", "seq", "act_embed")
    a = attn.self_attention(
        p["attn"], norm(p["ln1"], h, cfg), cfg, ctx,
        positions=positions, is_global=is_global, chunk=opts.attn_chunk,
        banded=banded)
    h = h + a
    hn = norm(p["ln2"], h, cfg)
    if cfg.n_experts:
        f = moe_mod.moe_ffn(p["moe"], hn, cfg, ctx)
        aux = moe_mod.router_aux_loss(p["moe"], hn, cfg)
    else:
        f = mlp(p["mlp"], hn, cfg, ctx)
        aux = jnp.zeros((), jnp.float32)
    return h + f, aux


def dense_block_decode(p, h, k_cache, v_cache, cfg: ArchConfig,
                       ctx: ShardCtx, *, pos, at, is_global=True,
                       use_kernel: bool = False, experts=None):
    """One-token step.  Returns (h, k_cache, v_cache, held).

    ``k_cache``/``v_cache`` are the whole KV stacks and ``at`` this layer's
    leading indices into them; they come back with this layer's rows
    written in place (``attention.decode_self_attention``).  ``pos`` may be
    scalar (lockstep) or ``(B,)`` per-slot positions.

    An MoE layer is the dropless held-experts layer
    (``moe.held_experts_ffn``): its expert weights are ``experts``, the
    stacks of every layer, read at ``at[0]``.  ``held`` is each token's
    held-expert bits, ``(B, 1)`` int32; None for a dense layer.
    ``use_kernel`` computes both with the Pallas kernels.
    """
    a, k_cache, v_cache = attn.decode_self_attention(
        p["attn"], norm(p["ln1"], h, cfg), k_cache, v_cache, cfg, ctx,
        pos=pos, at=at, is_global=is_global, use_kernel=use_kernel)
    h = h + a
    hn = norm(p["ln2"], h, cfg)
    held = None
    if cfg.n_experts:
        f, held = moe_mod.held_experts_ffn(
            p["moe"], hn, cfg, experts=experts, layer=at[0],
            use_kernel=use_kernel)
    else:
        f = mlp(p["mlp"], hn, cfg, ctx)
    return h + f, k_cache, v_cache, held


# ---------------------------------------------------------------------------
# Cross-attention block (VLM)
# ---------------------------------------------------------------------------
def cross_block_spec(cfg: ArchConfig) -> dict:
    return {
        "ln": rmsnorm_spec(cfg.d_model),
        "xattn": attn.attn_spec(cfg, cross=True),
        "gate": rmsnorm_spec(cfg.d_model),   # tanh-gated residual scale
    }


def cross_block(p, h, img: jax.Array, cfg: ArchConfig, ctx: ShardCtx,
                opts: ModelOpts):
    a = attn.cross_attention(p["xattn"], rmsnorm(p["ln"], h), img, cfg, ctx,
                             chunk=opts.attn_chunk)
    gate = jnp.tanh(p["gate"]["scale"].astype(a.dtype))
    return h + a * gate


def cross_block_cached(p, h, xk, xv, cfg: ArchConfig, ctx: ShardCtx):
    """Decode path: image KV already projected and cached."""
    q = attn.project_q(p["xattn"], rmsnorm(p["ln"], h), cfg)
    o = attn.chunked_mha(q, xk, xv, ctx, causal=False, chunk=1)
    a = attn.out_proj(p["xattn"], o, cfg)
    gate = jnp.tanh(p["gate"]["scale"].astype(a.dtype))
    return h + a * gate


# ---------------------------------------------------------------------------
# Mamba block wrapper
# ---------------------------------------------------------------------------
def mamba_block_spec(cfg: ArchConfig) -> dict:
    return {"ln": rmsnorm_spec(cfg.d_model), "mixer": ssm_mod.mamba_spec(cfg)}


def mamba_block(p, h, cfg: ArchConfig, ctx: ShardCtx, opts: ModelOpts):
    h = ctx.constrain(h, "batch", "seq", "act_embed")
    return h + ssm_mod.mamba_block(p["mixer"],
                                   rmsnorm(p["ln"], h, cfg.norm_eps), cfg,
                                   ctx, use_kernel=opts.use_kernel)


def mamba_block_decode(p, h, cache, cfg: ArchConfig, ctx: ShardCtx):
    y, cache = ssm_mod.mamba_decode_step(
        p["mixer"], rmsnorm(p["ln"], h, cfg.norm_eps), cache, cfg, ctx)
    return h + y, cache
