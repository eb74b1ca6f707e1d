"""Mamba2 (arXiv:2405.21060) language model: pre-norm Mamba2 blocks, final
RMSNorm, LM head tied to the embedding.

``reference_hidden`` is the plain float32 reference, written from the
paper's recurrent form of SSD, one token after another, and from the
published block (``mamba_ssm`` Mamba2 defaults, ``ngroups=1``): in_proj
gives [z, x, B, C, dt]; a causal depthwise conv of width ``d_conv`` with
bias and SiLU runs over [x, B, C]; ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``; the state update is ``h = exp(dt A) h + dt x B^T``
and the output ``y = h C + D x``; then ``RMSNorm(y * silu(z))`` and
out_proj.  The residual stream stays in float32 (``residual_in_fp32``).
Departures: none in the mathematics; weights are the served bf16 ones,
upcast.  ``mm`` is the matrix product; the control swaps in a lower
precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def normalize(cfg: dict) -> dict:
    """The sizes the harness needs, from the model's ``config.json`` and
    the Mamba2 layer's defaults (``ssm_layer`` in the configuration file)."""
    layer = cfg["ssm_layer"]
    mult = cfg["pad_vocab_size_multiple"]
    vocab = -(-cfg["vocab_size"] // mult) * mult
    d_inner = layer["expand"] * cfg["d_model"]
    return {
        "layers": cfg["n_layer"],
        "d_model": cfg["d_model"],
        "vocab": vocab,                        # padded rows, as the source
        "token_vocab": cfg["vocab_size"],      # ids the traffic draws from
        "d_inner": d_inner,
        "d_state": layer["d_state"],
        "d_conv": layer["d_conv"],
        "head_dim": layer["headdim"],
        "ssm_heads": d_inner // layer["headdim"],
        "chunk": layer["chunk_size"],
        "expand": layer["expand"],
        "tie_embeddings": cfg["tie_embeddings"],
        "eps": float(layer["norm_epsilon"]),
        "dtype": cfg["served_dtype"],
    }


def program_config(c: dict, name: str) -> dict:
    """Keyword arguments of ``repro.configs.base.ArchConfig``."""
    return dict(name=name, family="ssm", n_layers=c["layers"],
                d_model=c["d_model"], n_heads=0, n_kv_heads=0, head_dim=0,
                d_ff=0, vocab=c["vocab"], ssm_state=c["d_state"],
                ssm_head_dim=c["head_dim"], ssm_expand=c["expand"],
                ssm_conv_width=c["d_conv"], ssm_chunk=c["chunk"],
                tie_embeddings=c["tie_embeddings"], dtype=c["dtype"])


def layout(c: dict) -> dict:
    """Leaf path -> (shape, kind) of the weights (``weights.make``)."""
    L, d, v = c["layers"], c["d_model"], c["vocab"]
    di, n, h, w = c["d_inner"], c["d_state"], c["ssm_heads"], c["d_conv"]
    conv = di + 2 * n
    out = {
        "embed/tok": ((v, d), "embed"),
        "ln_f/scale": ((d,), "scale"),
        "layers/ln/scale": ((L, d), "scale"),
        "layers/mixer/in_proj": ((L, d, 2 * di + 2 * n + h), "matrix"),
        "layers/mixer/conv_w": ((L, w, conv), "conv"),
        "layers/mixer/conv_b": ((L, conv), "bias"),
        "layers/mixer/A_log": ((L, h), "a_log"),
        "layers/mixer/D": ((L, h), "scale"),
        "layers/mixer/dt_bias": ((L, h), "dt_bias"),
        "layers/mixer/norm/scale": ((L, di), "scale"),
        "layers/mixer/out_proj": ((L, di, d), "matrix"),
    }
    if not c["tie_embeddings"]:
        out["embed/unembed"] = ((d, v), "matrix")
    return out


def matmul_params(c: dict) -> int:
    """Weights in matrix products per token, LM head in, embedding out."""
    d, di, n, h = c["d_model"], c["d_inner"], c["d_state"], c["ssm_heads"]
    per_layer = d * (2 * di + 2 * n + h) + di * d
    return c["layers"] * per_layer + d * c["vocab"]


def flops_per_token(c: dict, context: int) -> float:
    """2 x matmul weights, plus per layer the conv (2 x width x channels) and
    the state update and read-out (decay, outer product and C-contraction:
    6 x heads x head_dim x state).  Context does not enter."""
    di, n = c["d_inner"], c["d_state"]
    state = c["ssm_heads"] * c["head_dim"] * n
    per_layer = 2 * c["d_conv"] * (di + 2 * n) + 6 * state
    return 2.0 * matmul_params(c) + c["layers"] * per_layer


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def unembed(w: dict, c: dict) -> jax.Array:
    return w["embed"]["tok"].T if c["tie_embeddings"] else \
        w["embed"]["unembed"]


def reference_hidden(w: dict, tokens: jax.Array, c: dict, mm) -> jax.Array:
    """tokens (N, S) -> final-normed hidden states (N, S, D), float32."""
    f32 = jnp.float32
    N, S = tokens.shape
    di, n, H, P, W = (c["d_inner"], c["d_state"], c["ssm_heads"],
                      c["head_dim"], c["d_conv"])
    eps = c["eps"]
    h = jnp.take(w["embed"]["tok"], tokens, axis=0).astype(f32)

    def layer(h, lw):
        m = lw["mixer"]
        x = _rms(h, lw["ln"]["scale"], eps)
        proj = mm(x, m["in_proj"])
        z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * n], \
            proj[..., 2 * di + 2 * n:]
        taps = m["conv_w"].astype(f32)                 # (W, channels)
        padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
        conv = sum(padded[:, j:j + S] * taps[j] for j in range(W))
        xbc = jax.nn.silu(conv + m["conv_b"].astype(f32))
        xs = xbc[..., :di].reshape(N, S, H, P)
        Bm, Cm = xbc[..., di:di + n], xbc[..., di + n:]
        dt = jax.nn.softplus(dt + m["dt_bias"].astype(f32))     # (N, S, H)
        A = -jnp.exp(m["A_log"].astype(f32))

        def token(state, inp):
            x_t, b_t, c_t, dt_t = inp
            decay = jnp.exp(dt_t * A)                           # (N, H)
            state = state * decay[..., None, None] + \
                (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
            y = jnp.einsum("nhpk,nk->nhp", state, c_t)
            return state, y

        state0 = jnp.zeros((N, H, P, n), f32)
        seq = (xs.transpose(1, 0, 2, 3), Bm.transpose(1, 0, 2),
               Cm.transpose(1, 0, 2), dt.transpose(1, 0, 2))
        _, ys = jax.lax.scan(token, state0, seq)
        y = ys.transpose(1, 0, 2, 3) + xs * m["D"].astype(f32)[:, None]
        y = y.reshape(N, S, di) * jax.nn.silu(z)
        y = _rms(y, m["norm"]["scale"], eps)
        return h + mm(y, m["out_proj"]), None

    h, _ = jax.lax.scan(layer, h, w["layers"])
    return _rms(h, w["ln_f"]["scale"], eps)
