"""phi3.5-moe-42b-a6.6b — 16-expert top-2 MoE (PhiMoE).

32 layers, d_model=4096, 32 heads (GQA kv=8) of 128, per-expert d_ff=6400,
vocab=32064, MoE FFN in every layer.  The block as published
(``modeling_phimoe.py`` of Hugging Face ``transformers``): LayerNorm with
weight and bias at eps 1e-5 (``rms_norm_eps``) before attention, before the
experts and after the last layer; biases on q, k, v and o
(``attention_bias``); a bias-free router of width 16 whose top-2 is chosen
by sparsemixer with eps 0.01 (``router_jitter_noise``); an untied LM head
with bias (``lm_head_bias``).  RoPE is plain at theta 10000: the published
``longrope`` factors are not in this repository.
[hf:microsoft/Phi-3.5-MoE-instruct; hf]

Served with float32 activations against the bf16 weights (``act_dtype``):
sparsemixer's choices are discontinuous in the router's logits, which read
the residual stream, so rounding that stream to bf16 sends tokens to other
experts than the model's own float routing picks.  This is the reason the
Switch Transformer keeps its router in float32 ("selective precision",
Fedus et al., arXiv:2101.03961, sec. 2.4); sparsemixer needs the router's
input at that precision too.  On one TPU v5e serving this model's one-chip
share (8 layers, 8 of 16 experts, B=64), bf16 activations left 19-24% of
the served tokens off a float32 reference's first choice, float32
activations 0-3.3%, at the same step time: the decode step is bound by
reading the experts, and the two-part products only add MXU work.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="phi3.5-moe-42b-a6.6b",
    family="moe",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=6400,
    vocab=32064,
    n_experts=16,
    top_k=2,
    activation="swiglu",
    qkv_bias=True,
    attn_out_bias=True,
    norm="layer",
    norm_eps=1e-5,
    router="sparsemixer",
    router_eps=0.01,
    lm_head_bias=True,
    act_dtype="float32",
)
