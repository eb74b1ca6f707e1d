"""Qwen2-style dense decoder (Qwen1.5): RMSNorm, multi-head attention with
QKV bias and RoPE, SwiGLU MLP, untied LM head.

``reference_hidden`` is the plain float32 reference, written from the
published description (the Qwen2 decoder layer of Hugging Face
``transformers``), not from the code under test.  Departures:

- attention is exact softmax attention over the whole padded sequence with
  a causal mask; no cache, no kernel, no batching of slots;
- every weight matrix and the embedding are read from the served bf16
  weights and upcast, which is what a deployment would serve.

``mm`` is the matrix product; the control swaps in a lower-precision one.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def normalize(cfg: dict) -> dict:
    """The sizes the harness needs, from a Hugging Face ``config.json``."""
    heads = cfg["num_attention_heads"]
    return {
        "layers": cfg["num_hidden_layers"],
        "d_model": cfg["hidden_size"],
        "d_ff": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"],
        "token_vocab": cfg["vocab_size"],
        "n_heads": heads,
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // heads,
        "qkv_bias": True,                      # Qwen2 attention always has it
        "tie_embeddings": cfg["tie_word_embeddings"],
        "rope_theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "dtype": cfg["torch_dtype"],
    }


def program_config(c: dict, name: str) -> dict:
    """Keyword arguments of ``repro.configs.base.ArchConfig``."""
    return dict(name=name, family="dense", n_layers=c["layers"],
                d_model=c["d_model"], n_heads=c["n_heads"],
                n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"],
                d_ff=c["d_ff"], vocab=c["vocab"], qkv_bias=True,
                activation="swiglu", rope_theta=c["rope_theta"],
                tie_embeddings=c["tie_embeddings"], dtype=c["dtype"])


def layout(c: dict) -> dict:
    """Leaf path -> (shape, kind) of the weights (``weights.make``)."""
    L, d, f, v = c["layers"], c["d_model"], c["d_ff"], c["vocab"]
    qd = c["n_heads"] * c["head_dim"]
    kd = c["n_kv_heads"] * c["head_dim"]
    out = {
        "embed/tok": ((v, d), "embed"),
        "ln_f/scale": ((d,), "scale"),
        "layers/ln1/scale": ((L, d), "scale"),
        "layers/ln2/scale": ((L, d), "scale"),
        "layers/attn/wq": ((L, d, qd), "matrix"),
        "layers/attn/wk": ((L, d, kd), "matrix"),
        "layers/attn/wv": ((L, d, kd), "matrix"),
        "layers/attn/wo": ((L, qd, d), "matrix"),
        "layers/mlp/wi": ((L, d, f), "matrix"),
        "layers/mlp/wg": ((L, d, f), "matrix"),
        "layers/mlp/wo": ((L, f, d), "matrix"),
    }
    if c["qkv_bias"]:
        out.update({"layers/attn/bq": ((L, qd), "bias"),
                    "layers/attn/bk": ((L, kd), "bias"),
                    "layers/attn/bv": ((L, kd), "bias")})
    if not c["tie_embeddings"]:
        out["embed/unembed"] = ((d, v), "matrix")
    return out


def matmul_params(c: dict) -> int:
    """Weights in matrix products per token, LM head in, embedding out."""
    d, f = c["d_model"], c["d_ff"]
    qd = c["n_heads"] * c["head_dim"]
    kd = c["n_kv_heads"] * c["head_dim"]
    per_layer = d * qd + 2 * d * kd + qd * d + 3 * d * f
    return c["layers"] * per_layer + d * c["vocab"]


def flops_per_token(c: dict, context: int) -> float:
    """2 x matmul weights + 4 x layers x context x q_dim (scores and values)."""
    qd = c["n_heads"] * c["head_dim"]
    return 2.0 * matmul_params(c) + 4.0 * c["layers"] * context * qd


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (N, S, H, Dh); rotate-half RoPE at positions 0..S-1."""
    S, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    return x * cos + rotated * sin


def unembed(w: dict, c: dict) -> jax.Array:
    """(D, V) output projection as served (bf16)."""
    return w["embed"]["tok"].T if c["tie_embeddings"] else \
        w["embed"]["unembed"]


def reference_hidden(w: dict, tokens: jax.Array, c: dict, mm) -> jax.Array:
    """tokens (N, S) -> final-normed hidden states (N, S, D), float32."""
    f32 = jnp.float32
    N, S = tokens.shape
    H, K, Dh, eps = c["n_heads"], c["n_kv_heads"], c["head_dim"], c["eps"]
    h = jnp.take(w["embed"]["tok"], tokens, axis=0).astype(f32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(h, lw):
        a = lw["attn"]
        x = _rms(h, lw["ln1"]["scale"], eps)
        q = (mm(x, a["wq"]) + a["bq"].astype(f32)).reshape(N, S, H, Dh)
        k = (mm(x, a["wk"]) + a["bk"].astype(f32)).reshape(N, S, K, Dh)
        v = (mm(x, a["wv"]) + a["bv"].astype(f32)).reshape(N, S, K, Dh)
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / math.sqrt(Dh)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v)
        h = h + mm(o.reshape(N, S, H * Dh), a["wo"])
        x = _rms(h, lw["ln2"]["scale"], eps)
        m = lw["mlp"]
        h = h + mm(jax.nn.silu(mm(x, m["wg"])) * mm(x, m["wi"]), m["wo"])
        return h, None

    h, _ = jax.lax.scan(layer, h, w["layers"])
    return _rms(h, w["ln_f"]["scale"], eps)
