"""The PhiMoE family at reduced widths on the CPU: ``BatchedServer`` on the
expert kernel's path against the plain float32 reference
(``families/moe.py``), sparsemixer at near-ties, the held share of the
experts against the uncut layer, the routing counters and their readers,
the published block, and a tiny cell through the harness."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import correctness as C
import expert_counts
import programspans
import tinybench
import weights as W
from families import moe as ref

#: a reduced Phi block: d 64, 4 layers, 4 experts of which 2 are held
#: (from expert 1), GQA 4:1 with heads of 16
MOE = {"bench_family": "moe", "num_attention_heads": 4,
       "num_key_value_heads": 1, "num_hidden_layers": 4, "hidden_size": 64,
       "intermediate_size": 128, "vocab_size": 512, "attention_bias": True,
       "lm_head_bias": True, "num_experts_per_tok": 2,
       "num_local_experts": 2, "router_experts": 4, "first_expert": 1,
       "router_jitter_noise": 0.01, "rms_norm_eps": 1e-05,
       "rope_theta": 10000.0, "tie_word_embeddings": False,
       "torch_dtype": "bfloat16"}
#: the published ``config.json`` keys of microsoft/Phi-3.5-MoE-instruct
#: that the family reads
PHI = {"bench_family": "moe", "num_attention_heads": 32,
       "num_key_value_heads": 8, "num_hidden_layers": 32, "hidden_size": 4096,
       "intermediate_size": 6400, "vocab_size": 32064, "attention_bias": True,
       "lm_head_bias": True, "num_experts_per_tok": 2, "num_local_experts": 16,
       "router_jitter_noise": 0.01, "rms_norm_eps": 1e-05,
       "rope_theta": 10000.0, "tie_word_embeddings": False,
       "torch_dtype": "bfloat16"}
#: the tiny cell's widest-gap limit, between what serving bf16 weights with
#: float32 activations reads (at most 0.068 over seeds 1-8 of
#: ``test_float32_activations_within_limit_fp8_control_fails``'s sizes) and
#: what the fp8 control reads (at least 1.21 there)
LIMIT = 0.3
#: limit on the share of served tokens off the reference's argmax at bf16:
#: seeds 1-8 of ``test_bf16_server_within_limit_fp8_control_fails``'s sizes
#: read 1.9-5.5% served and 18.5-28.3% for the fp8 control, while their
#: widest gaps overlap (served up to 1.43, control down to 1.31): a route
#: flipped by bf16 rounding moves a token by a whole expert's output
OFF_ARGMAX_LIMIT = 0.10


def _program(c):
    from repro.configs.base import ArchConfig
    return ArchConfig(**ref.program_config(c, "tiny-moe"))


def _serve(dtype, seed, n_req=7, batch=3, max_seq=64, new=(4, 12),
           late_at=3, act_dtype=None):
    """Serve ``n_req`` requests through ``batch`` slots on the kernel path:
    the first ``batch`` at once, the rest submitted ``late_at`` steps in
    (admitted mid-flight, into slots others leave).  Returns weights,
    config, requests and the logits of every decode step with the requests
    in the slots at that step.  ``act_dtype`` is the activations' dtype
    (None: the weights')."""
    from repro.models.blocks import ModelOpts
    from repro.models.model import build_model
    from repro.runtime.serve import BatchedServer, Request
    c = dict(ref.normalize(MOE), dtype=dtype, act_dtype=act_dtype)
    model = build_model(_program(c))
    w = W.make(ref.layout(c), seed, jnp.dtype(dtype))
    server = BatchedServer(model, w, batch_size=batch, max_seq=max_seq,
                           opts=ModelOpts(remat="none"), use_kernel=True)
    seen = []
    decode = server._decode

    def spy(*args):
        out = decode(*args)
        seen.append((np.asarray(out[0]), list(server.active), server.steps))
        return out

    server._decode = spy
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, c["token_vocab"],
                                        int(rng.integers(3, 20))).tolist(),
                    max_new_tokens=int(rng.integers(*new)))
            for i in range(n_req)]
    for r in reqs[:batch]:
        server.submit(r)
    for _ in range(late_at):
        server.step()
    for r in reqs[batch:]:
        server.submit(r)
    server.drain()
    return w, c, reqs, seen, server


def _reference_logits(w, c, reqs, max_seq):
    toks = np.zeros((len(reqs), max_seq), np.int32)
    for n, r in enumerate(reqs):
        seq = r.prompt + r.output[:-1]
        toks[n, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        h = ref.reference_hidden(w, jnp.asarray(toks), c, C.mm_f32)
        return np.asarray(C.mm_f32(h, ref.unembed(w, c)))


def test_server_logits_match_reference_through_slot_reuse():
    """Every step's logits, prompt feed and decode alike, in every occupied
    slot: slots reused, requests admitted mid-flight while others decode.
    1e-4 of the largest logit: both sides compute in float32 (the reference
    at "highest" precision), so they differ by summation order only, about
    1e-6; a wrong bias, norm, route or cache row moves logits by 1e-2 or
    more at these widths."""
    w, c, reqs, seen, server = _serve("float32", seed=11)
    want = _reference_logits(w, c, reqs, 64)
    index = {id(r): n for n, r in enumerate(reqs)}
    worst, compared, reused, mid = 0.0, 0, set(), set()
    for logits, active, step in seen:
        busy = [r for r in active if r is not None]
        for slot, r in enumerate(active):
            if r is None:
                continue
            got, ref_row = logits[slot], want[index[id(r)], step - r.started]
            worst = max(worst, np.abs(got - ref_row).max() /
                        np.abs(ref_row).max())
            compared += 1
            if r.rid >= 3:
                reused.add(slot)
                if len(busy) > 1 and r.started > 0:
                    mid.add(r.rid)
    assert compared > 50 and worst <= 1e-4
    assert reused == {0, 1, 2} and mid
    assert server.held_rows > 0 and server.expert_loads > 0


def test_routing_counters_count_occupied_slots():
    """``routed_rows`` is occupied slots x top_k x layers; ``held_rows`` the
    pairs on held experts, about 2 of 4 experts' share of them; every
    (layer, held expert) takes a row at most once a step."""
    *_, server = _serve("bfloat16", seed=3)
    c = ref.normalize(MOE)
    assert server.routed_rows == server.slot_steps * 2 * c["layers"]
    assert 0.25 < server.held_rows / server.routed_rows < 0.75
    assert server.expert_loads <= server.steps * c["layers"] * 2
    assert server.expert_loads <= server.held_rows


@pytest.mark.parametrize("seed", (1, 2, 3))
def test_bf16_server_within_limit_fp8_control_fails(seed):
    """Served at bf16 as the configuration states, few tokens leave the
    float32 reference's argmax; the fp8 control leaves it several times as
    often (``OFF_ARGMAX_LIMIT``)."""
    w, c, reqs, _, _ = _serve("bfloat16", seed, n_req=12, new=(20, 40))
    served = [C.Served(r.prompt, r.output) for r in reqs]
    g = C.gaps(ref, w, c, served, 64, control="fp8")
    off = {k: float((v > 0).mean()) for k, v in g.items()}
    assert off["served"] <= OFF_ARGMAX_LIMIT < off["control"]


@pytest.mark.parametrize("seed", (1, 2, 3))
def test_float32_activations_within_limit_fp8_control_fails(seed):
    """bf16 weights served with float32 activations, as the Phi deployment
    serves them: the widest gap stays under ``LIMIT`` and the fp8 control's
    passes it."""
    w, c, reqs, _, _ = _serve("bfloat16", seed, n_req=12, new=(20, 40),
                              act_dtype="float32")
    served = [C.Served(r.prompt, r.output) for r in reqs]
    g = C.gaps(ref, w, c, served, 64, control="fp8")
    assert g["served"].max() <= LIMIT < g["control"].max()


NEAR_TIES = [
    # logits, weights, ids.  Near-tied top two: the second is within 2 eps
    # of the first, so the first choice's softmax keeps both.
    ([1.0, 0.995, 0.5, -1.0], [np.e / (np.e + np.exp(0.995)), 1.0], [0, 1]),
    # an exact tie goes to the first index, at weight one half
    ([2.0, -1.0, 2.0, 0.0], [0.5, 1.0], [0, 2]),
    # negative logits: the drop is measured against |s|
    ([-1.0, -1.01, -3.0, -1.5], [1 / (1 + np.exp(-0.01)), 1.0], [0, 1]),
    # just past 2 eps below the max: dropped from the first softmax
    ([1.0, 0.979, 0.0, 0.0], [1.0, 1.0], [0, 1]),
]


@pytest.mark.parametrize("logits,weights,ids", NEAR_TIES)
def test_sparsemixer_near_ties(logits, weights, ids):
    """The program's sparsemixer and the reference's agree with the values
    worked by hand from the published definition, at near-tied logits."""
    from repro.models.moe import sparsemixer
    x = jnp.asarray([logits], jnp.float32)
    for w_got, i_got in (sparsemixer(x, 2, 0.01), ref.sparsemixer(x, 0.01)):
        np.testing.assert_allclose(np.asarray(w_got)[0], weights, rtol=1e-6)
        assert np.asarray(i_got)[0].tolist() == ids


@pytest.mark.parametrize("use_kernel", (False, True))
def test_held_shares_sum_to_the_uncut_layer(use_kernel):
    """Two chips holding experts 0-1 and 2-3 of a 4-expert layer: the parts
    each computes, summed, are the uncut reference's whole layer (float32;
    1e-5 of its largest value, summation order apart)."""
    import dataclasses
    from repro.models.moe import held_experts_ffn
    c = dict(ref.normalize(dict(MOE, num_local_experts=4, first_expert=0)),
             dtype="float32")
    m = W.make(ref.layout(c), 5, jnp.float32)["layers"]["moe"]
    m = jax.tree.map(lambda a: a[0], m)                  # one layer
    x = jax.random.normal(jax.random.PRNGKey(0), (6, c["d_model"]))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.held_experts(m, x, c, C.mm_f32))
        parts = 0.0
        for first in (0, 2):
            cfg = dataclasses.replace(_program(c), held_experts=(first, 2))
            p = {"router": m["router"],
                 **{k: m[k][first:first + 2] for k in ("wg", "wi", "wo")}}
            y, _ = held_experts_ffn(p, x, cfg, use_kernel=use_kernel)
            parts = parts + np.asarray(y)
    np.testing.assert_allclose(parts, whole, atol=1e-5 * np.abs(whole).max())


def _trace(steps, kernels, layers=4):
    """A made-up trace: ``steps`` serve.step spans with routing counters
    (8 routed and 3 held rows, 2 expert loads a step), ``kernels`` device
    ops named as the expert kernel, 1 ms each."""
    stats = lambda i: {"step": i, "routed_rows": 8 * i,      # noqa: E731
                       "held_rows": 3 * i, "expert_loads": 2 * i}
    return programspans.from_json({
        "devices": {"/device:TPU:0": [
            [f"%expert_ffn.{layers} = f32[4,64]{{1,0}} custom-call(%a)",
             0.1 + 0.002 * k, 0.101 + 0.002 * k] for k in range(kernels)]},
        "spans": [["bench.step", 0.0, 1.0]],
        "program": [["serve.step", 0.01 * i, 0.01 * i + 0.005, stats(i)]
                    for i in range(steps)]})


@pytest.mark.parametrize("act_dtype,row_bytes", ((None, 2), ("float32", 4)))
def test_held_share_and_expert_roofline_read_the_counters(act_dtype,
                                                          row_bytes):
    from cellspec import metric_reader

    class Run:
        trace = _trace(steps=11, kernels=40)
        c = dict(ref.normalize(MOE), act_dtype=act_dtype)
        peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}

    assert expert_counts.growth(Run.trace) == {
        "step": 10, "routed_rows": 80, "held_rows": 30, "expert_loads": 20}
    assert metric_reader("held_share.moe_chat")(Run, None) == \
        pytest.approx(37.5)
    # a step: 3 held rows, 2 expert loads, against 4 kernels of 1 ms
    flops, nbytes = expert_counts.expert_ffn(
        3, 2, d_model=64, d_ff=128, weight_bytes=2, row_bytes=row_bytes)
    assert nbytes == 3 * 64 * 128 * 2 * 2 + 2 * 64 * row_bytes * 3
    assert flops == 6 * 64 * 128 * 3
    assert metric_reader("expert_ffn_roofline.moe_chat")(Run, None) == \
        pytest.approx(100 * max(flops / 1e12, nbytes / 1e9) / 4e-3)
    # a program that counts no routes has nothing to read
    Run.trace = programspans.from_json({
        "devices": {}, "spans": [["bench.step", 0.0, 1.0]],
        "program": [["serve.step", 0.1, 0.2, {"step": 0}],
                    ["serve.step", 0.3, 0.4, {"step": 1}]]})
    assert metric_reader("held_share.moe_chat")(Run, None) is None
    assert metric_reader("expert_ffn_roofline.moe_chat")(Run, None) is None


def test_flops_per_token_counts_the_expected_held_pairs():
    c = ref.normalize(MOE)
    attn = 64 * 64 + 2 * 64 * 16 + 64 * 64 + 64 * 4
    experts = 2 * 3 * 64 * 128 * (2 * 2 / 4)
    assert ref.flops_per_token(c, 10) == pytest.approx(
        2 * (4 * attn + 64 * 512) + 4 * 4 * 10 * 64 + experts * 4)


def test_published_sizes_and_memory():
    """The cell's configuration file holds the published keys but for the
    two it lists as reduced; ``configs/phi35_moe.py`` states the block the
    reference builds from it, float32 activations included; one chip's
    share of the 8-chip deployment (layers 0-7, experts 0-7 of 16) holds
    11.3 GB of bf16 weights."""
    from repro.configs import REGISTRY
    with open(os.path.join(tinybench.REPO, "bench", "configs",
                           "phi3.5-moe-42b-a6.6b.json")) as f:
        cfg = json.load(f)
    assert {k: cfg[k] for k in PHI} == dict(PHI, num_hidden_layers=8,
                                            num_local_experts=8)
    assert cfg["published"] == {"num_hidden_layers": 32,
                                "num_local_experts": 16}
    c = ref.normalize(cfg)
    assert c["n_experts"] == 16 and c["held"] == (0, 8) and c["layers"] == 8
    want = _program(c)
    have = dataclasses.replace(REGISTRY["phi3.5-moe-42b-a6.6b"], n_layers=8,
                               held_experts=(0, 8))
    for f in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab",
              "qkv_bias", "attn_out_bias", "norm", "norm_eps", "activation",
              "n_experts", "top_k", "router", "router_eps", "held_experts",
              "rope_theta", "tie_embeddings", "lm_head_bias", "dtype",
              "act_dtype"):
        assert getattr(have, f) == getattr(want, f), f
    n = sum(int(np.prod(s)) for s, _ in ref.layout(c).values())
    assert 2 * n == pytest.approx(11.3e9, rel=0.01)
    assert n == pytest.approx(have.n_params(), rel=1e-4)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The tiny copy of the benchmark with a tiny MoE cell beside the
    others (``tinybench``), served as the Phi cell is (bf16 weights, float32
    activations) and reporting its metrics."""
    root = tinybench.make(tmp_path_factory.mktemp("bench"))
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny-moe.json"), "w") as f:
        json.dump(dict(MOE, activation_dtype="float32"), f)
    with open(os.path.join(b, "cells", "tiny-moe.chat.json"), "w") as f:
        json.dump({"batch": 3, "max_seq": 64, "use_kernel": True,
                   "rate_rps": 30.0, "trace_seconds": 1, "drain_cap_s": 30,
                   "sample_requests": 4,
                   "limits": {"widest_gap": LIMIT, "min_tokens": 8}}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "reduced": [], "why": "test",
                             "file": "bench/configs/tiny-moe.json"})
    bench["workloads"].append({"name": "tiny-moe.chat", "config": "tiny-moe",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "phi3.5-moe-42b-a6.6b.chat" in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["tiny-moe.chat"]
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", (0, 1))
def test_tiny_moe_cell_result_line(checkout, capsys, trace):
    """The harness runs the family by name: ``correct``, the end-to-end
    metrics untraced, and traced the server loop's spans and the counters'
    share (the CPU has no device plane, so the device metrics are
    absent)."""
    run = tinybench.load_run(checkout)
    run.require_devices = lambda chips: jax.devices()
    run.peaks_for = lambda kind: {"bf16_flops_per_s": 1e12,
                                  "hbm_bytes_per_s": 1e11}
    run.use_compile_cache = lambda: "off"
    capsys.readouterr()
    assert run.main(["--workload", "tiny-moe.chat", "--seed",
                     str(2**31 + 9), "--seconds", "2",
                     "--trace", str(trace)]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    if trace:
        assert {"step_ms.moe_chat", "dispatch_ms.moe_chat",
                "admit_walk_ms.moe_chat"} <= set(res["metrics"])
        assert 0 < res["metrics"]["held_share.moe_chat"]["value"] < 100
        assert "expert_ffn_roofline.moe_chat" not in res["metrics"]
    else:
        assert set(res["metrics"]) == {"setup_s", "itl_p95_ms"}
