"""The plain float32 references against ``BatchedServer`` at reduced widths
on the CPU: logits agree through slot reuse (the decode kernel included for
the dense family), served tokens stay within the limit, and a reference
served in fp8, the control, does not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import correctness as C
import tinybench
import weights as W
from families import dense, ssm

CASES = [(dense, tinybench.DENSE, True), (ssm, tinybench.SSM, False)]
IDS = ["dense-kernel", "ssm"]
#: RMSNorm epsilon of the program's Mamba2 block, fixed in the program;
#: the configuration states Mamba2's published 1e-5
PROGRAM_EPS = 1e-6


def _serve(family, hf, use_kernel, dtype, seed, n_req=7, batch=3,
           max_seq=64, new=(4, 12)):
    """Serve ``n_req`` requests through ``batch`` slots (so slots are
    reused); return weights, config, requests and the logits of every
    decode step with the requests in the slots at that step."""
    from repro.configs.base import ArchConfig
    from repro.models.blocks import ModelOpts
    from repro.models.model import build_model
    from repro.runtime.serve import BatchedServer, Request
    c = dict(family.normalize(hf), dtype=dtype)
    model = build_model(ArchConfig(**family.program_config(c, "t")))
    w = W.make(family.layout(c), seed, jnp.dtype(dtype))
    server = BatchedServer(model, w, batch_size=batch, max_seq=max_seq,
                           opts=ModelOpts(remat="none"),
                           use_kernel=use_kernel)
    seen = []
    decode = server._decode

    def spy(*args):
        out = decode(*args)
        seen.append((np.asarray(out[0]), list(server.active),
                     server.steps))
        return out

    server._decode = spy
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, c["token_vocab"],
                                        int(rng.integers(3, 20))).tolist(),
                    max_new_tokens=int(rng.integers(*new)))
            for i in range(n_req)]
    server.run(reqs)
    return w, c, reqs, seen


def _reference_logits(family, w, c, reqs, max_seq):
    toks = np.zeros((len(reqs), max_seq), np.int32)
    for n, r in enumerate(reqs):
        seq = r.prompt + r.output[:-1]
        toks[n, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        h = family.reference_hidden(w, jnp.asarray(toks), c, C.mm_f32)
        return np.asarray(C.mm_f32(h, family.unembed(w, c)))


def _worst_rel_error(seen, ref, reqs):
    """Largest |served - reference| logit over each position's largest
    reference logit, for every slot of every step; and the slots that held
    a request after another had left them."""
    index = {id(r): n for n, r in enumerate(reqs)}
    worst, compared, reused = 0.0, 0, set()
    for logits, active, step in seen:
        for slot, r in enumerate(active):
            if r is None:
                continue
            got, want = logits[slot], ref[index[id(r)], step - r.started]
            worst = max(worst, np.abs(got - want).max() / np.abs(want).max())
            compared += 1
            if r.rid >= 3:
                reused.add(slot)
    assert compared > 50
    return worst, reused


@pytest.mark.parametrize("family,hf,use_kernel", CASES, ids=IDS)
def test_server_logits_match_reference_through_slot_reuse(family, hf,
                                                          use_kernel):
    w, c, reqs, seen = _serve(family, hf, use_kernel, "float32", seed=11)
    if family is ssm:         # the program's own departure, tested below
        c = dict(c, eps=PROGRAM_EPS)
    worst, reused = _worst_rel_error(seen, _reference_logits(
        family, w, c, reqs, 64), reqs)
    assert worst <= 1e-4 and reused == {0, 1, 2}


def test_program_mamba2_departs_from_published_norm_epsilon():
    """The program's Mamba2 block fixes RMSNorm's epsilon at 1e-6 where the
    configuration states 1e-5: in float32 its logits match the reference
    run at 1e-6 and miss the published one.  This is why no Mamba2 cell is
    in the benchmark until the program follows the configuration."""
    w, c, reqs, seen = _serve(ssm, tinybench.SSM, False, "float32", seed=11)
    assert c["eps"] == 1e-5
    err = {eps: _worst_rel_error(seen, _reference_logits(
        ssm, w, dict(c, eps=eps), reqs, 64), reqs)[0]
        for eps in (c["eps"], PROGRAM_EPS)}
    assert err[PROGRAM_EPS] <= 1e-4 < err[c["eps"]]


@pytest.mark.parametrize("family,hf,use_kernel", CASES, ids=IDS)
def test_bf16_server_within_limit_fp8_control_fails(family, hf, use_kernel):
    limit = tinybench.LIMITS[family.__name__.rsplit(".", 1)[-1]]
    for seed in (1, 2, 3):
        w, c, reqs, _ = _serve(family, hf, use_kernel, "bfloat16", seed,
                               n_req=12, new=(20, 40))
        served = [C.Served(r.prompt, r.output) for r in reqs]
        g = C.gaps(family, w, c, served, 64, control="fp8")
        assert g["served"].max() <= limit < g["control"].max(), seed


def test_sample_holds_the_longest_and_follows_the_seed():
    done = [C.Served([1] * n, [2] * 3) for n in (5, 9, 30, 7, 11, 4)]
    a = C.sample(done, 3, seed=2**33 + 1)
    assert a[0].tokens == 33 and len(a) == 3
    assert [x.tokens for x in a] == \
        [x.tokens for x in C.sample(done, 3, seed=2**33 + 1)]
    assert C.sample([], 3, seed=1) == []
    assert len(C.sample(done, 10, seed=1)) == 6
