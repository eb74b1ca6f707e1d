"""Mixture-of-Experts FFN with grouped, gather-only dispatch.

TPU-native adaptation (DESIGN.md §2): tokens are processed in G groups
aligned with the data-parallel shards (GShard-style grouping).  Within each
group, top-k routing slots are ordered by expert via an argsort, and the
(expert, capacity) buffers are built with *gathers only* — no scatters, no
(tokens × experts × capacity) one-hot dispatch tensor.  This matters because
XLA SPMD partitions batched gathers cleanly (group dim sharded over 'data',
expert dim over 'model') whereas cross-shard scatter-adds replicate their
operands (observed: 150 GB/chip peaks with the scatter formulation).

Expert parallelism: the expert dim of the weights and buffers shards over
the 'model' mesh axis; the all-to-all implied by (tokens grouped by data
shard) × (experts owned by model shards) is inserted by SPMD at the gather
boundaries.  Over-capacity tokens are dropped (capacity-factor semantics),
so ``moe_ffn`` serves the training path only.

Serving (decode and prefill) takes ``held_experts_ffn``: the layer is told
which experts it holds (``cfg.held``), routes every token over all
``n_experts`` and returns the held experts' part of the result, dropping no
token.  Pairs routed to experts held elsewhere add nothing here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distrib.logical import P, ShardCtx
from repro.kernels import twopart


def moe_spec(cfg: ArchConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.held[1]
    return {
        "router": P((d, cfg.n_experts), ("embed", "experts")),
        "wi": P((e, d, f), ("experts", "embed", "ffn")),
        "wg": P((e, d, f), ("experts", "embed", "ffn")),
        "wo": P((e, f, d), ("experts", "ffn", "embed")),
    }


def capacity(cfg: ArchConfig, tokens_per_group: int) -> int:
    c = int(cfg.capacity_factor * tokens_per_group * cfg.top_k
            / cfg.n_experts)
    return max(8, ((c + 127) // 128) * 128)      # MXU-aligned


def _num_groups(batch: int) -> int:
    # aligned with the data-parallel shards (pod×data = 32 at multi-pod)
    g = min(32, batch)
    while batch % g:
        g -= 1
    return g


def moe_ffn(p, x: jax.Array, cfg: ArchConfig, ctx: ShardCtx) -> jax.Array:
    """x: (B, S, D) -> (B, S, D)."""
    if cfg.held[1] != cfg.n_experts:
        raise NotImplementedError(
            "the capacity-factor layer computes every expert; a config that "
            "holds some of them serves through held_experts_ffn")
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    G = _num_groups(B)
    Tg = (B // G) * S
    C = capacity(cfg, Tg)
    dt = x.dtype

    xg = x.reshape(G, Tg, D)
    xg = ctx.constrain(xg, "batch", None, "act_embed")

    # --- routing (f32) ---
    logits = xg.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # (G, Tg, E)
    gate_w, gate_ids = jax.lax.top_k(probs, K)              # (G, Tg, K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # --- order slots by expert within each group ---
    flat_ids = gate_ids.reshape(G, Tg * K)                  # (G, N)
    order = jnp.argsort(flat_ids, axis=-1)                  # (G, N)
    sorted_ids = jnp.take_along_axis(flat_ids, order, axis=-1)
    inv_order = jnp.argsort(order, axis=-1)                 # slot -> sorted pos

    # segment starts per expert (batched searchsorted)
    seg_start = jax.vmap(
        lambda row: jnp.searchsorted(row, jnp.arange(E), side="left")
    )(sorted_ids)                                           # (G, E)
    seg_end = jax.vmap(
        lambda row: jnp.searchsorted(row, jnp.arange(E), side="right")
    )(sorted_ids)

    # --- expert buffers via gather ---
    # slot_pos[g, e, c] = position in the sorted slot array
    slot_pos = seg_start[:, :, None] + jnp.arange(C)[None, None]   # (G,E,C)
    slot_valid = slot_pos < seg_end[:, :, None]
    slot_pos = jnp.minimum(slot_pos, Tg * K - 1)
    slot_token = jnp.take_along_axis(
        order.reshape(G, Tg * K), slot_pos.reshape(G, E * C), axis=-1
    ).reshape(G, E, C) // K                                 # token index

    buf = jnp.take_along_axis(
        xg[:, None].astype(dt),                             # (G,1,Tg,D)
        slot_token[..., None],                              # (G,E,C,1)
        axis=2)                                             # (G,E,C,D)
    buf = jnp.where(slot_valid[..., None], buf, 0)
    buf = ctx.constrain(buf, "batch", "experts", "expert_cap", "act_embed")

    # --- expert FFNs (E sharded over 'model') ---
    act = jax.nn.silu if cfg.activation == "swiglu" else (
        lambda a: jax.nn.gelu(a, approximate=True))
    h = act(jnp.einsum("gecd,edf->gecf", buf, p["wg"].astype(dt))) \
        * jnp.einsum("gecd,edf->gecf", buf, p["wi"].astype(dt))
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["wo"].astype(dt))
    out_buf = ctx.constrain(out_buf, "batch", "experts", "expert_cap",
                            "act_embed")

    # --- combine back (gathers only) ---
    # for each (token, k) slot: its sorted position -> (expert, capacity)
    sorted_pos = inv_order.reshape(G, Tg, K)                # (G, Tg, K)
    e_of = gate_ids                                         # (G, Tg, K)
    c_of = sorted_pos - jnp.take_along_axis(
        seg_start, e_of.reshape(G, Tg * K), axis=-1).reshape(G, Tg, K)
    valid = c_of < C
    lin = (e_of * C + jnp.clip(c_of, 0, C - 1)).reshape(G, Tg * K)
    y_slots = jnp.take_along_axis(
        out_buf.reshape(G, E * C, D), lin[..., None], axis=1)
    y_slots = y_slots.reshape(G, Tg, K, D)
    y_slots = jnp.where(valid[..., None], y_slots, 0)
    y = jnp.sum(y_slots.astype(jnp.float32)
                * gate_w[..., None], axis=2)                # (G, Tg, D)
    return y.astype(dt).reshape(B, S, D)


def router_aux_loss(p, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style): E * sum_e f_e * p_e."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D).astype(jnp.float32)
    probs = jax.nn.softmax(xt @ p["router"].astype(jnp.float32), axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, cfg.n_experts, dtype=jnp.float32), 0)
    pbar = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(f * pbar)


# ---------------------------------------------------------------------------
# Serving: route over every expert, compute the held ones, drop nothing
# ---------------------------------------------------------------------------
def sparsemixer(logits: jax.Array, top_k: int, eps: float):
    """PhiMoE's sparsemixer as at inference: (..., E) -> weights, ids (..., K).

    For each choice in turn: take the largest remaining logit m, mask every
    logit s with (m - s) / max(|s|, m) > 2 eps, take the softmax over what
    is left and select the argmax with its probability as its weight; then
    set the chosen logit to -inf for the next choice.
    """
    scores = logits.astype(jnp.float32)
    left = scores
    weights, ids = [], []
    for _ in range(top_k):
        m = jnp.max(left, axis=-1, keepdims=True)
        idx = jnp.argmax(left, axis=-1)
        far = (m - scores) / jnp.maximum(jnp.abs(scores), m) > 2 * eps
        p = jax.nn.softmax(jnp.where(far, -jnp.inf, left), axis=-1)
        weights.append(jnp.take_along_axis(p, idx[..., None], -1)[..., 0])
        ids.append(idx)
        left = jnp.where(jnp.arange(scores.shape[-1]) == idx[..., None],
                         -jnp.inf, left)
    return jnp.stack(weights, -1), jnp.stack(ids, -1).astype(jnp.int32)


def route(router: jax.Array, x: jax.Array, cfg: ArchConfig):
    """x: (..., D) -> (weights float32, expert ids int32), each (..., top_k),
    over all ``cfg.n_experts`` router outputs, in float32."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if cfg.router == "sparsemixer":
        return sparsemixer(logits, cfg.top_k, cfg.router_eps)
    w, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    return w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9), ids


def held_gates(weights: jax.Array, ids: jax.Array, cfg: ArchConfig):
    """(..., K) routes -> (gates (..., E_held) float32, held bits (...,)
    int32).

    ``gates[..., e]`` is the token's weight for held expert ``e`` (0 if not
    routed to it); bit ``e`` of ``held`` is set where it was.
    """
    first, count = cfg.held
    local = ids - first
    mine = (local >= 0) & (local < count)
    onehot = (local[..., None] == jnp.arange(count)) & mine[..., None]
    gates = jnp.sum(jnp.where(onehot, weights[..., None], 0.0), axis=-2)
    bits = jnp.where(mine, jnp.left_shift(1, jnp.clip(local, 0, 30)), 0)
    return gates, jnp.sum(bits, axis=-1).astype(jnp.int32)


def held_experts_ffn(p, x: jax.Array, cfg: ArchConfig, *, experts=None,
                     layer=None, use_kernel: bool = False):
    """The held experts' part of the MoE layer, dropping no token.

    x: (..., D), routed in float32 and multiplied through the experts in
    their weights' dtype, at float32 precision where x is float32 and the
    weights narrower (``twopart``); ``p`` holds this layer's ``router`` (D,
    n_experts) and, unless ``experts`` is given, its held experts' ``wg``,
    ``wi`` (E_held, D, F) and ``wo`` (E_held, F, D).  ``experts`` are the
    (L, E_held, ...) stacks of every layer, read at ``layer``; with
    ``use_kernel`` the ``expert_ffn`` Pallas kernel reads them where they
    lie.  Every token goes through every held expert, weighted by its gate,
    so the work does not depend on the routing.  -> (y (..., D) in x's
    dtype, held bits (...,) int32: the held experts each token was routed
    to).
    """
    if cfg.activation != "swiglu":
        raise NotImplementedError("held experts are SwiGLU")
    lead, D = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, D)
    gates, bits = held_gates(*route(p["router"], xt, cfg), cfg)
    if experts is None:                  # this layer's own: a stack of one
        experts, layer = {k: p[k][None] for k in ("wg", "wi", "wo")}, 0
    wg, wi, wo = experts["wg"], experts["wi"], experts["wo"]
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        y = kernel_ops.expert_ffn(xt, gates, wg, wi, wo, layer)
    else:
        wg, wi, wo = wg[layer], wi[layer], wo[layer]
        dt = wg.dtype
        split = twopart.wider(xt.dtype, dt)

        def dot(v, m):                 # (n, k) @ (e, k, f) per expert
            if split:
                return jax.vmap(twopart.matmul, (None, 0))(v, m)
            return jnp.einsum("nk,ekf->enf", v.astype(dt), m,
                              preferred_element_type=jnp.float32)

        h = jax.nn.silu(dot(xt, wg)) * dot(xt, wi) * gates.T[..., None]
        if split:
            y = jax.vmap(twopart.matmul)(h, wo).sum(0)
        else:
            y = jnp.einsum("enf,efd->nd", h.astype(dt), wo,
                           preferred_element_type=jnp.float32)
    return y.astype(x.dtype).reshape(x.shape), bits.reshape(lead)
