"""The benchmark's count functions against the operations XLA compiles
(``repro.analysis.hlo_cost``), and the peaks table."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import counts
import tinybench
from families import dense, ssm
from repro.analysis.hlo_cost import analyze_compiled


def _attention(q, k, v):
    s = jnp.einsum("bhd,bhsd->bhs", q, k)
    return jnp.einsum("bhs,bhsd->bhd", jax.nn.softmax(s, -1), v)


def test_decode_attention_counts_match_compiled_attention():
    """At full length the algorithm's FLOPs are the two contractions XLA
    compiles (plus softmax, about 4%), and its bytes are K, V, q and o,
    which the compiled program reads and writes with more besides."""
    B, H, S, D = 3, 4, 64, 16
    args = [jnp.ones((B, H, D)), jnp.ones((B, H, S, D)),
            jnp.ones((B, H, S, D))]
    cost = analyze_compiled(jax.jit(_attention).lower(*args).compile())
    flops, nbytes = counts.decode_attention(
        [S] * B, n_heads=H, n_kv_heads=H, head_dim=D, layers=1,
        kv_bytes=4, q_bytes=4)
    assert flops == 4 * B * S * H * D
    assert 0.9 * cost.flops <= flops <= cost.flops
    assert 0.6 * cost.bytes <= nbytes <= cost.bytes


def test_decode_attention_counts_scale_with_length_not_capacity():
    f_short, b_short = counts.decode_attention(
        [10, 30], n_heads=20, n_kv_heads=20, head_dim=128, layers=40,
        kv_bytes=4, q_bytes=4)
    f_long, b_long = counts.decode_attention(
        [20, 60], n_heads=20, n_kv_heads=20, head_dim=128, layers=40,
        kv_bytes=4, q_bytes=4)
    assert f_long == 2 * f_short
    kv = 40 * 2 * 40 * 20 * 128 * 4
    assert b_long - b_short == pytest.approx(kv)


@pytest.mark.parametrize("family,hf", [(dense, tinybench.DENSE),
                                       (ssm, tinybench.SSM)])
def test_model_flops_per_token_match_compiled_decode_step(family, hf):
    """The count is the matrix products (and the state or attention
    arithmetic) of the decode step the server compiles; what XLA counts on
    top is elementwise work, a minority even at these tiny widths."""
    from repro.configs.base import ArchConfig
    from repro.distrib.logical import NOSHARD
    from repro.models.blocks import ModelOpts
    from repro.models.model import build_model
    c = family.normalize(hf)
    model = build_model(ArchConfig(**family.program_config(c, "t")))
    B, S = 4, 64
    cache = jax.eval_shape(lambda: model.init_cache(B, S, jnp.float32))
    step = jax.jit(lambda p, t, pos, kv: model.decode_step(
        p, {"token": t, "pos": pos}, kv, NOSHARD, ModelOpts(remat="none")))
    cost = analyze_compiled(step.lower(
        model.abstract_params(jnp.bfloat16),
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32), cache).compile())
    counted = B * family.flops_per_token(c, S)
    assert 0.6 * cost.flops <= counted <= cost.flops
    assert family.matmul_params(c) * 2 * B <= counted


def test_qwen_published_sizes():
    with open(os.path.join(tinybench.REPO, "bench", "configs",
                           "qwen1.5-4b.json")) as f:
        c = dense.normalize(json.load(f))
    # 3.95 B parameters in all; the embedding gather is not a product
    assert dense.matmul_params(c) + c["vocab"] * c["d_model"] == \
        pytest.approx(3.95e9, rel=0.01)
    assert dense.flops_per_token(c, 0) == 2 * dense.matmul_params(c)


def test_peaks_table_keyed_by_device_kind():
    with open(os.path.join(tinybench.REPO, "bench", "peaks.json")) as f:
        table = json.load(f)
    v5e = table["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    flops, nbytes = 197e12, 819e9 * 2
    assert counts.roofline_s(flops, nbytes, v5e) == pytest.approx(2.0)
