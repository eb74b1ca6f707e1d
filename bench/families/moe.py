"""PhiMoE decoder (Phi-3.5-MoE): LayerNorm with bias, GQA attention with
biases on q, k, v and o and RoPE, a sparsemixer top-2 router over every
expert, SwiGLU experts, untied LM head with bias.

``reference_hidden`` is the plain float32 reference, written from the
published description (``PhiMoEDecoderLayer`` and ``sparsemixer`` of
Hugging Face ``transformers``' ``modeling_phimoe.py``), not from the code
under test.  Departures:

- RoPE is plain at ``rope_theta``: the published ``longrope`` short factors
  and attention scale are not in this repository (the config lists this
  under ``assumed``); the program does the same;
- the chip holds a share of every layer's experts (the config's
  ``num_local_experts`` of the router's ``router_experts``, from
  ``first_expert``): tokens are routed over all of them and only the held
  experts' contributions are added, as in the program; what the absent
  experts would add is left out of both;
- attention is exact softmax attention over the whole padded sequence with
  a causal mask; no cache, no kernel, no batching of slots; each layer's
  held experts are computed one at a time over every position, each
  weighted by its gate (0 where the token was not routed to it);
- every weight matrix and the embedding are read from the served bf16
  weights and upcast, which is what a deployment would serve.

The harness takes logits as ``hidden @ unembed``.  So that they carry the
LM head's bias, ``reference_hidden`` appends a column of ones to the final
normed hidden state and ``unembed`` appends the bias as the last row of the
head.

``mm`` is the matrix product; the control swaps in a lower-precision one.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def normalize(cfg: dict) -> dict:
    """The sizes the harness needs, from the configuration file: the
    published ``config.json`` keys, with ``num_local_experts`` the experts
    held here out of ``router_experts``, starting at ``first_expert``, and
    ``activation_dtype`` (if given) the deployment's activations."""
    heads = cfg["num_attention_heads"]
    if cfg["num_experts_per_tok"] != 2:
        raise ValueError("sparsemixer chooses two experts")
    return {
        "layers": cfg["num_hidden_layers"],
        "d_model": cfg["hidden_size"],
        "d_ff": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"],
        "token_vocab": cfg["vocab_size"],
        "n_heads": heads,
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // heads,
        "attention_bias": cfg["attention_bias"],
        "lm_head_bias": cfg["lm_head_bias"],
        "n_experts": cfg["router_experts"],
        "held": (cfg["first_expert"], cfg["num_local_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "router_eps": float(cfg["router_jitter_noise"]),
        "rope_theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg["torch_dtype"],
        "act_dtype": cfg.get("activation_dtype"),
    }


def program_config(c: dict, name: str) -> dict:
    """Keyword arguments of ``repro.configs.base.ArchConfig``."""
    return dict(name=name, family="moe", n_layers=c["layers"],
                d_model=c["d_model"], n_heads=c["n_heads"],
                n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"],
                d_ff=c["d_ff"], vocab=c["vocab"],
                qkv_bias=c["attention_bias"],
                attn_out_bias=c["attention_bias"], norm="layer",
                norm_eps=c["eps"], activation="swiglu",
                n_experts=c["n_experts"], top_k=c["top_k"],
                router="sparsemixer", router_eps=c["router_eps"],
                held_experts=tuple(c["held"]), rope_theta=c["rope_theta"],
                tie_embeddings=False, lm_head_bias=c["lm_head_bias"],
                act_dtype=c["act_dtype"], dtype=c["dtype"])


def layout(c: dict) -> dict:
    """Leaf path -> (shape, kind) of the weights (``weights.make``)."""
    L, d, f, v = c["layers"], c["d_model"], c["d_ff"], c["vocab"]
    qd = c["n_heads"] * c["head_dim"]
    kd = c["n_kv_heads"] * c["head_dim"]
    e = c["held"][1]
    out = {
        "embed/tok": ((v, d), "embed"),
        "embed/unembed": ((d, v), "matrix"),
        "ln_f/scale": ((d,), "scale"),
        "ln_f/bias": ((d,), "bias"),
        "layers/ln1/scale": ((L, d), "scale"),
        "layers/ln1/bias": ((L, d), "bias"),
        "layers/ln2/scale": ((L, d), "scale"),
        "layers/ln2/bias": ((L, d), "bias"),
        "layers/attn/wq": ((L, d, qd), "matrix"),
        "layers/attn/wk": ((L, d, kd), "matrix"),
        "layers/attn/wv": ((L, d, kd), "matrix"),
        "layers/attn/wo": ((L, qd, d), "matrix"),
        "layers/moe/router": ((L, d, c["n_experts"]), "matrix"),
        "layers/moe/wg": ((L, e, d, f), "matrix"),
        "layers/moe/wi": ((L, e, d, f), "matrix"),
        "layers/moe/wo": ((L, e, f, d), "matrix"),
    }
    if c["attention_bias"]:
        out.update({"layers/attn/bq": ((L, qd), "bias"),
                    "layers/attn/bk": ((L, kd), "bias"),
                    "layers/attn/bv": ((L, kd), "bias"),
                    "layers/attn/bo": ((L, d), "bias")})
    if c["lm_head_bias"]:
        out["embed/unembed_bias"] = ((v,), "bias")
    return out


def flops_per_token(c: dict, context: int) -> float:
    """2 x (attention, router and LM-head weights) + 4 x layers x context x
    q_dim (scores and values) + 2 x 3 x d x f for each of the top_k x held /
    n_experts (token, expert) pairs a token is expected to send to the
    experts held here, in every layer."""
    d, f = c["d_model"], c["d_ff"]
    qd = c["n_heads"] * c["head_dim"]
    kd = c["n_kv_heads"] * c["head_dim"]
    per_layer = d * qd + 2 * d * kd + qd * d + d * c["n_experts"]
    held_pairs = c["top_k"] * c["held"][1] / c["n_experts"]
    return (2.0 * (c["layers"] * per_layer + d * c["vocab"])
            + 4.0 * c["layers"] * context * qd
            + 2.0 * 3 * d * f * held_pairs * c["layers"])


def _layernorm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * \
        p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _rope(x, theta):
    """x: (N, S, H, Dh); rotate-half RoPE at positions 0..S-1."""
    S, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _choose(scores, masked, eps):
    """One sparsemixer choice: the argmax of ``masked`` and its softmax
    weight after dropping the logits too far below the max, measured
    against the unmasked ``scores``."""
    top = jnp.max(masked, axis=-1, keepdims=True)
    ind = jnp.argmax(masked, axis=-1)
    factor = jnp.maximum(jnp.abs(scores), top)
    drop = (top - scores) / factor > 2 * eps
    gates = jax.nn.softmax(jnp.where(drop, -jnp.inf, masked), axis=-1)
    return jnp.take_along_axis(gates, ind[..., None], -1)[..., 0], ind


def sparsemixer(scores, eps):
    """(..., E) router logits -> (weights, ids), each (..., 2), as
    ``sparsemixer`` computes them at inference (no jitter)."""
    w1, i1 = _choose(scores, scores, eps)
    masked = jnp.where(jnp.arange(scores.shape[-1]) == i1[..., None],
                       -jnp.inf, scores)
    w2, i2 = _choose(scores, masked, eps)
    return jnp.stack([w1, w2], -1), jnp.stack([i1, i2], -1)


def held_experts(m: dict, x: jax.Array, c: dict, mm) -> jax.Array:
    """The held experts' part of one MoE layer: x (..., D) routed over all
    ``n_experts`` router outputs, then for each held expert in turn its
    gate (0 where the token was not routed to it) times its SwiGLU output.
    ``m`` holds the layer's ``router`` and the held experts' ``wg``, ``wi``
    (E_held, D, F) and ``wo`` (E_held, F, D)."""
    first, count = c["held"]
    weights, ids = sparsemixer(mm(x, m["router"]), c["router_eps"])

    def expert(y, ew):
        wg, wi, wo, e = ew
        gate = jnp.sum(jnp.where(ids == first + e, weights, 0.0), -1)
        out = mm(jax.nn.silu(mm(x, wg)) * mm(x, wi), wo)
        return y + gate[..., None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros(x.shape, jnp.float32),
                        (m["wg"], m["wi"], m["wo"], jnp.arange(count)))
    return y


def unembed(w: dict, c: dict) -> jax.Array:
    """(D + 1, V) output projection as served (bf16), the LM head's bias
    as its last row (``reference_hidden`` ends in a column of ones)."""
    head = w["embed"]["unembed"]
    bias = w["embed"]["unembed_bias"] if c["lm_head_bias"] else \
        jnp.zeros(head.shape[-1], head.dtype)
    return jnp.concatenate([head, bias[None].astype(head.dtype)], axis=0)


def reference_hidden(w: dict, tokens: jax.Array, c: dict, mm) -> jax.Array:
    """tokens (N, S) -> final-normed hidden states with a column of ones
    appended (N, S, D + 1), float32."""
    f32 = jnp.float32
    N, S = tokens.shape
    H, K, Dh, eps = c["n_heads"], c["n_kv_heads"], c["head_dim"], c["eps"]
    h = jnp.take(w["embed"]["tok"], tokens, axis=0).astype(f32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def bias(a, name):
        return a[name].astype(f32) if c["attention_bias"] else 0.0

    def layer(h, lw):
        a = lw["attn"]
        x = _layernorm(h, lw["ln1"], eps)
        q = (mm(x, a["wq"]) + bias(a, "bq")).reshape(N, S, H, Dh)
        k = (mm(x, a["wk"]) + bias(a, "bk")).reshape(N, S, K, Dh)
        v = (mm(x, a["wv"]) + bias(a, "bv")).reshape(N, S, K, Dh)
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(Dh)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v)
        h = h + mm(o.reshape(N, S, H * Dh), a["wo"]) + bias(a, "bo")

        x = _layernorm(h, lw["ln2"], eps)
        return h + held_experts(lw["moe"], x, c, mm), None

    h, _ = jax.lax.scan(layer, h, w["layers"])
    h = _layernorm(h, w["ln_f"], eps)
    return jnp.concatenate([h, jnp.ones((N, S, 1), f32)], axis=-1)
