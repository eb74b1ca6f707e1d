"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode) +
hypothesis property tests on attention invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.decode_attention import block_size, decode_attention
from repro.kernels.expert_ffn import expert_ffn
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import decode_mha_ref, expert_ffn_ref, mha_ref, ssd_ref
from repro.kernels.ssd_scan import ssd_scan

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,Hq,Hkv,S,D,causal,window,dt", [
    (2, 4, 4, 256, 64, True, 0, jnp.float32),
    (1, 8, 2, 256, 64, True, 0, jnp.float32),
    (1, 8, 2, 256, 64, True, 0, jnp.bfloat16),
    (2, 4, 2, 512, 128, True, 128, jnp.float32),
    (1, 4, 1, 256, 64, True, 0, jnp.float32),      # MQA
    (1, 4, 4, 256, 64, False, 0, jnp.float32),     # bidirectional
    (1, 2, 2, 384, 64, True, 0, jnp.float32),      # non-pow2 seq
])
def test_flash_attention_sweep(B, Hq, Hkv, S, D, causal, window, dt):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), dt)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dt)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dt)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          bq=128, bk=128, interpret=True)
    ref = mha_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dt], rtol=TOL[dt])


@pytest.mark.parametrize("B,L,H,P,N,chunk,dt", [
    (2, 256, 3, 64, 32, 64, jnp.float32),
    (1, 512, 2, 64, 64, 128, jnp.float32),
    (2, 256, 4, 32, 16, 128, jnp.bfloat16),
    (1, 128, 1, 16, 8, 32, jnp.float32),
])
def test_ssd_scan_sweep(B, L, H, P, N, chunk, dt):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (B, L, H, P), dt) * 0.5
    dtv = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, N), dt) * 0.3
    Cm = jax.random.normal(ks[4], (B, L, N), dt) * 0.3
    D = jnp.ones((H,))
    y_k, s_k = ssd_scan(x, dtv, A, Bm, Cm, D, chunk=chunk, interpret=True)
    y_r, s_r = ssd_ref(x, dtv, A, Bm, Cm, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32),
                               atol=TOL[dt] * 5, rtol=TOL[dt] * 5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               atol=1e-4, rtol=1e-4)


def test_ssd_chunk_invariance():
    """The chunked algorithm must be exact: chunk size cannot change y."""
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    B, L, H, P, N = 1, 256, 2, 32, 16
    x = jax.random.normal(ks[0], (B, L, H, P)) * 0.5
    dtv = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, L, N)) * 0.3
    D = jnp.ones((H,))
    y64, _ = ssd_ref(x, dtv, A, Bm, Cm, D, chunk=64)
    y256, _ = ssd_ref(x, dtv, A, Bm, Cm, D, chunk=256)
    np.testing.assert_allclose(np.asarray(y64), np.asarray(y256),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,Hq,Hkv,S,D,length,dt", [
    (2, 8, 2, 1024, 64, 1000, jnp.float32),
    (1, 4, 4, 2048, 128, 1024, jnp.bfloat16),
    (1, 16, 2, 1024, 64, 17, jnp.float32),   # short effective length
])
def test_decode_attention_sweep(B, Hq, Hkv, S, D, length, dt):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, Hq, D), dt)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), dt)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), dt)
    out = decode_attention(q, k, v, length, bk=512, interpret=True)
    ref = decode_mha_ref(q, k, v, length=length)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dt], rtol=TOL[dt])


def test_decode_attention_precise_matches_float32():
    """``precise``: float32 q, K and V as two bf16 parts each, three exact
    products: the oracle's float32 result to float32's tolerance, where
    bf16 operands miss it by about 1e-3."""
    B, Hq, Hkv, S, D = 2, 8, 2, 1024, 128
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, Hq, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    length = jnp.array([1000, 17], jnp.int32)
    want = np.asarray(decode_mha_ref(q, k, v, length=length[:, None, None]))
    got = decode_attention(q, k, v, length, bk=512, precise=True,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=TOL[jnp.float32],
                               rtol=TOL[jnp.float32])
    rounded = decode_attention(*(x.astype(jnp.bfloat16).astype(jnp.float32)
                                 for x in (q, k, v)), length, bk=512,
                               interpret=True)
    assert np.abs(np.asarray(rounded) - want).max() > 1e-3


@pytest.mark.parametrize("Hq,Hkv", [(20, 20), (32, 8)])    # MHA, GQA
def test_decode_attention_reads_layer_of_stack(Hq, Hkv):
    """The kernel reads layer ``l`` of a stacked ``(L,B,Hkv,S,D)`` cache
    (the decode step's layer-scan carry) as it reads that slab alone:
    against the oracle on each layer's slab, with per-slot lengths."""
    L, B, S, D = 3, 3, 1024, 128
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, Hq, D))
    k = jax.random.normal(ks[1], (L, B, Hkv, S, D))
    v = jax.random.normal(ks[2], (L, B, Hkv, S, D))
    length = jnp.array([1000, 1, 517], jnp.int32)
    for layer in range(L):
        out = decode_attention(q, k, v, length, layer, bk=512,
                               interpret=True)
        ref = decode_mha_ref(q, k[layer], v[layer],
                             length=length[:, None, None])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=TOL[jnp.float32],
                                   rtol=TOL[jnp.float32])
        slab = decode_attention(q, k[layer], v[layer], length, bk=512,
                                interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(slab))


def _ragged(Hq, Hkv, bk, key=5, S=512, D=128):
    """q, K, V of five slots, the block size (``block_size``'s if ``bk`` is
    None) and per-slot lengths 1, bk-1, bk, bk+1 and S."""
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    q = jax.random.normal(ks[0], (5, Hq, D))
    k = jax.random.normal(ks[1], (5, Hkv, S, D))
    v = jax.random.normal(ks[2], (5, Hkv, S, D))
    bk = bk or block_size(S, Hkv, D, 4)
    assert bk < S
    return q, k, v, bk, jnp.array([1, bk - 1, bk, bk + 1, S], jnp.int32)


@pytest.mark.parametrize("precise", [False, True])
@pytest.mark.parametrize("bk", [None, 16])          # derived, explicit
@pytest.mark.parametrize("Hq,Hkv", [(20, 20), (32, 8)])    # MHA, GQA
def test_decode_attention_ragged_lengths(Hq, Hkv, bk, precise):
    """Per-slot lengths on and around block boundaries, at the block size
    derived from the shapes and at a small explicit one: the oracle's
    result over each slot's own positions.  ``precise`` keeps about 16
    significant bits of each operand (two bf16 parts), so it is held to
    2**-14."""
    q, k, v, bk, length = _ragged(Hq, Hkv, bk)
    out = decode_attention(q, k, v, length, bk=bk, precise=precise,
                           interpret=True)
    ref = decode_mha_ref(q, k, v, length=length[:, None, None])
    tol = 2.0 ** -14 if precise else TOL[jnp.float32]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("bk", [None, 16])
@pytest.mark.parametrize("Hq,Hkv", [(20, 20), (32, 8)])
def test_decode_attention_reads_no_block_past_length(Hq, Hkv, bk):
    """K and V are NaN from the first block boundary at or after each
    slot's length: the output is the clean one to the bit, so no block
    wholly past a slot's length is read (a masked score still multiplies
    V, and 0 * NaN is NaN)."""
    q, k, v, bk, length = _ragged(Hq, Hkv, bk)
    S = k.shape[2]
    past = jnp.arange(S)[None] >= (-(-length // bk) * bk)[:, None]
    poison = lambda x: jnp.where(past[:, None, :, None], jnp.nan, x)  # noqa: E731
    clean = decode_attention(q, k, v, length, bk=bk, interpret=True)
    out = decode_attention(q, poison(k), poison(v), length, bk=bk,
                           interpret=True)
    assert np.asarray(past).any(axis=1).tolist() == [True] * 4 + [False]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


def _routing(kind, B, E, key):
    """(B, E) gates: every token on two experts at random weights
    (uniform), all on expert 0 (one), or on none of the held ones
    (absent)."""
    if kind == "absent":
        return jnp.zeros((B, E), jnp.float32)
    if kind == "one":
        return jnp.zeros((B, E), jnp.float32).at[:, 0].set(1.0)
    pick = jax.random.permutation(key, jnp.tile(jnp.arange(E), (B, 1)),
                                  axis=1, independent=True)[:, :2]
    w = jax.random.uniform(key, (B, 2), minval=0.2, maxval=1.0)
    return jnp.zeros((B, E)).at[jnp.arange(B)[:, None], pick].set(w)


def _pallas_grid(fn, *args):
    """(grid, block shapes) of the one pallas_call ``fn`` traces."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                return gm.grid, [tuple(bm.block_shape)
                                 for bm in gm.block_mappings]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                got = find(sub)
                if got:
                    return got
        return None
    return find(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("dt,split", [(jnp.float32, False),
                                      (jnp.bfloat16, False),
                                      (jnp.bfloat16, True)])
def test_expert_ffn_fixed_work_under_any_routing(dt, split):
    """The held-experts kernel against a plain per-expert loop under
    uniform, all-to-one-expert and all-to-absent routing, reading layer 1
    of a stack; its grid and block shapes are the same in all three.  With
    ``split``, float32 tokens against bf16 weights come out at float32's
    tolerance."""
    L, B, E, D, F = 2, 16, 4, 128, 384
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (B, D), jnp.float32 if split else dt)
    wg = (jax.random.normal(ks[1], (L, E, D, F)) / D ** 0.5).astype(dt)
    wi = (jax.random.normal(ks[2], (L, E, D, F)) / D ** 0.5).astype(dt)
    wo = (jax.random.normal(ks[3], (L, E, F, D)) / F ** 0.5).astype(dt)
    shapes = set()
    for kind in ("uniform", "one", "absent"):
        g = _routing(kind, B, E, ks[4])
        run = lambda *a: expert_ffn(*a, 1, tf=128,  # noqa: E731
                                    interpret=True)
        out = run(x, g, wg, wi, wo)
        want = expert_ffn_ref(x, g, wg[1], wi[1], wo[1])
        # unsplit bf16: the SwiGLU output is rounded to bf16 before wo
        t = TOL[jnp.float32 if split else dt]
        tol = t * float(jnp.abs(want).max() + 1.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=tol, rtol=t)
        if kind == "absent":
            assert not np.asarray(out).any()
        shapes.add(repr(_pallas_grid(run, x, g, wg, wi, wo)))
    assert len(shapes) == 1 and "(4, 3)" in shapes.pop()


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_attention_is_convex_combination(seed):
    """Property: each output vector lies in the convex hull of V rows —
    max |o| <= max |v| row-wise (softmax weights sum to 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32))
    k = jax.random.normal(ks[1], (1, 2, 128, 32))
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    o = flash_attention(q, k, v, causal=True, bq=128, bk=128,
                        interpret=True)
    assert float(jnp.max(jnp.abs(o))) <= float(jnp.max(jnp.abs(v))) + 1e-4


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_window_equals_causal_when_window_covers_seq(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 32))
    k = jax.random.normal(ks[1], (1, 2, 128, 32))
    v = jax.random.normal(ks[2], (1, 2, 128, 32))
    a = flash_attention(q, k, v, causal=True, window=0, interpret=True)
    b = flash_attention(q, k, v, causal=True, window=128, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
