"""Continuous-batching server + config router + deprecation shims.

The serving contract: on closed batches without slot reuse the
continuous server's greedy outputs are bit-identical to the retained
lockstep reference (per-slot positions coincide with the shared
position, and the generalized mask keeps the numerics bitwise
unchanged).  Off that regime the continuous server must do strictly
better — mid-flight admission at correct positions, per-slot
truncation, recurrent-state reset on slot reuse — exactly where the
lockstep loop was wrong or wasteful.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.core.objectives import EvalFailure, bind_objective
from repro.core.registry import get_method
from repro.exp import experiment_engine, make_engine, make_objective_engine
from repro.exp.runners import drive_units
from repro.models.blocks import ModelOpts
from repro.models.model import build_model
from repro.multicloud import build_dataset
from repro.multicloud.market import MarketClock, get_overlay
from repro.runtime.router import ConfigRouter
from repro.runtime.serve import BatchedServer, LockstepServer, Request

OPTS = ModelOpts(attn_chunk=32, remat="none")


def _model(arch):
    cfg = REGISTRY[arch].reduced()
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _reqs(n, base=3, gen=5):
    return [Request(rid=i, prompt=[1 + i, base, base + i % 3],
                    max_new_tokens=gen) for i in range(n)]


@pytest.fixture(scope="module")
def dense():
    return _model("qwen1.5-4b")


@pytest.fixture(scope="module")
def moe():
    return _model("phi3.5-moe-42b-a6.6b")


@pytest.fixture(scope="module")
def ssm():
    return _model("mamba2-130m")


@pytest.fixture(scope="module")
def ds():
    return build_dataset()


# ---------------------------------------------------------------------------
# Closed-batch bit-identity vs the lockstep reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fixture", ("dense", "ssm"))
def test_closed_batch_bit_identical_to_lockstep(fixture, request):
    model, params = request.getfixturevalue(fixture)
    B = 3
    lock = LockstepServer(model, params, batch_size=B, max_seq=64,
                          opts=OPTS)
    cont = BatchedServer(model, params, batch_size=B, max_seq=64,
                         opts=OPTS)
    ref = lock.run(_reqs(B))
    out = cont.run(_reqs(B))
    assert out == ref               # greedy tokens, bit-identical


def test_partial_batch_bit_identical(dense):
    model, params = dense
    lock = LockstepServer(model, params, batch_size=4, max_seq=64,
                          opts=OPTS)
    cont = BatchedServer(model, params, batch_size=4, max_seq=64,
                         opts=OPTS)
    assert cont.run(_reqs(2)) == lock.run(_reqs(2))


def _staggered(srv, late_at=4):
    """Serve requests of different lengths, two of them admitted mid-flight
    (after ``late_at`` steps) into slots that others have freed, so slots
    sit at different positions and are reused."""
    for r in _reqs(3, gen=3):
        srv.submit(r)
    out = {}
    for _ in range(late_at):
        out.update((r.rid, r.output) for r in srv.step())
    srv.submit(Request(rid=10, prompt=[7, 8, 9, 10, 11], max_new_tokens=7))
    srv.submit(Request(rid=11, prompt=[12], max_new_tokens=9))
    return {**out, **srv.drain()}


def test_kernel_path_matches_reference(dense):
    """The kernel path (cache in the layer scan's carry, rows written in
    place, the kernel reading each layer's slab out of the stack) gives the
    reference path's greedy tokens: closed batches with slot reuse, and
    mid-flight admission at per-slot positions."""
    model, params = dense
    ref = BatchedServer(model, params, batch_size=2, max_seq=64,
                        opts=OPTS, use_kernel=False)
    ker = BatchedServer(model, params, batch_size=2, max_seq=64,
                        opts=OPTS, use_kernel=True)
    assert ker.use_kernel
    assert ker.run(_reqs(4)) == ref.run(_reqs(4))
    out = _staggered(ker)
    assert out == _staggered(ref)
    assert set(out) == {0, 1, 2, 10, 11}
    assert [len(out[i]) for i in (10, 11)] == [7, 9]


# (fixture, use_kernel): qwen's cases keep their ids; Phi-3.5-MoE's read
# the expert stacks at each layer's index on both paths
PREFILL_CASES = [pytest.param("dense", k, id=str(k)) for k in (False, True)] \
    + [pytest.param("moe", k, id=f"phi3.5-moe-{k}") for k in (False, True)]


@pytest.mark.parametrize("fixture,use_kernel", PREFILL_CASES)
def test_prefill_cache_feeds_decode_step(fixture, use_kernel, request):
    """``Model.prefill`` emits the head-major cache that ``decode_step``
    reads: padded along its position axis, it continues the sequence (at
    per-slot positions) as the full prefill does, and it holds the rows
    that decoding the same tokens one at a time (lockstep) writes."""
    model, params = request.getfixturevalue(fixture)
    n, S = 11, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, n + 1), 0,
                              model.cfg.vocab)
    _, pc = model.prefill(params, {"tokens": toks[:, :n]}, opts=OPTS)
    full, _ = model.prefill(params, {"tokens": toks}, opts=OPTS)
    opts = dataclasses.replace(OPTS, use_kernel=use_kernel)
    pad = [(0, 0)] * 3 + [(0, S - n), (0, 0)]
    cache = {k: jnp.pad(c, pad) for k, c in pc.items()}
    assert cache["k"].shape == model.init_cache(2, S)["k"].shape
    lg, _ = model.decode_step(
        params, {"token": toks[:, n:], "pos": jnp.full((2,), n, jnp.int32)},
        cache, opts=opts)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full),
                               rtol=1e-2, atol=1e-2)
    dec = model.init_cache(2, S, jnp.float32)
    for i in range(n):              # lockstep: one shared (scalar) position
        _, dec = model.decode_step(
            params, {"token": toks[:, i:i + 1], "pos": jnp.int32(i)}, dec,
            opts=opts)
    for key in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(dec[key][..., :n, :]), np.asarray(pc[key]),
            rtol=1e-2, atol=1e-2)
        assert not np.asarray(dec[key][..., n:, :]).any()


# reduced vlm groups hold one self-attention layer each: two, here, so the
# (group, layer) index takes both its parts
GROUPED = {"zamba2-7b": {},
           "llama-3.2-vision-90b": dict(n_layers=6, cross_attn_every=3)}


@pytest.mark.parametrize("arch", sorted(GROUPED))
def test_grouped_decode_writes_the_rows_prefill_emits(arch):
    """The hybrid and vlm decode steps carry their KV stacks through the
    group scans and write each token's rows in place, at the group's index
    (hybrid's shared attention block) and at (group, layer) (vlm's self
    attention): decoding a sequence one token at a time at a scalar
    position ends at the logits ``Model.prefill`` gives and holds the K/V
    rows it emits, and nothing past them."""
    cfg = dataclasses.replace(REGISTRY[arch].reduced(), **GROUPED[arch])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n, S = 8, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, n), 0, cfg.vocab)
    batch = {"tokens": toks}
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (2, cfg.n_image_tokens, cfg.d_model))
    want, pc = model.prefill(params, batch, opts=OPTS)
    dec = model.init_cache(2, S, jnp.float32)
    if cfg.family == "vlm":             # the image's K/V, as prefill cached
        dec.update(xk=pc["xk"], xv=pc["xv"])
    for i in range(n):
        lg, dec = model.decode_step(
            params, {"token": toks[:, i:i + 1], "pos": jnp.int32(i)}, dec,
            opts=OPTS)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(want),
                               rtol=1e-2, atol=1e-2)
    for key in ("k", "v"):
        assert dec[key].shape[:-2] == pc[key].shape[:-2]
        np.testing.assert_allclose(
            np.asarray(dec[key][..., :n, :]), np.asarray(pc[key]),
            rtol=1e-2, atol=1e-2)
        assert not np.asarray(dec[key][..., n:, :]).any()


def test_kernel_refused_for_sliding_window():
    model, params = _model("gemma3-27b")      # sliding_window set
    with pytest.raises(ValueError, match="sliding window"):
        BatchedServer(model, params, batch_size=2, max_seq=64,
                      opts=OPTS, use_kernel=True)
    srv = BatchedServer(model, params, batch_size=2, max_seq=64,
                        opts=OPTS, use_kernel=False)
    assert len(srv.run(_reqs(2))) == 2


# ---------------------------------------------------------------------------
# Continuous-only behaviour: admission, truncation, slot reuse
# ---------------------------------------------------------------------------
def test_mid_flight_admission_position_independent(dense):
    """A request admitted into a half-finished batch decodes at its own
    position 0 — its output must equal serving it alone."""
    model, params = dense
    late = Request(rid=99, prompt=[7, 8, 9], max_new_tokens=6)
    solo = BatchedServer(model, params, batch_size=2, max_seq=64,
                         opts=OPTS)
    ref = solo.run([Request(rid=99, prompt=[7, 8, 9], max_new_tokens=6)])

    srv = BatchedServer(model, params, batch_size=2, max_seq=64,
                        opts=OPTS)
    for r in _reqs(2, gen=8):
        srv.submit(r)
    for _ in range(5):              # neighbours mid-generation
        srv.step()
    srv.submit(late)                # queued until a slot frees
    out = srv.drain()
    assert out[99] == ref[99]
    assert late.arrived == 5
    assert late.started > late.arrived      # waited for a slot
    assert set(out) == {0, 1, 99}


def test_per_slot_truncation_spares_neighbours(dense):
    """KV exhaustion truncates only the offending slot; the lockstep
    loop flushed the whole batch at S-1."""
    model, params = dense
    S = 24
    long = Request(rid=0, prompt=[5, 6], max_new_tokens=100)
    srv = BatchedServer(model, params, batch_size=2, max_seq=S, opts=OPTS)
    srv.submit(long)
    srv.step()                      # long occupies slot 0 first
    short = Request(rid=1, prompt=[9, 10], max_new_tokens=4)
    srv.submit(short)
    out = srv.drain()
    assert len(out[0]) < 100        # truncated at its own S-1
    assert len(out[1]) == 4         # neighbour unaffected
    assert not srv.queue and all(a is None for a in srv.active)


def test_ssm_slot_reuse_resets_recurrent_state(ssm):
    """The recurrent state must not leak across slot occupants: a
    request served in a reused slot equals serving it alone."""
    model, params = ssm
    mk = lambda: Request(rid=7, prompt=[11, 12], max_new_tokens=5)
    solo = BatchedServer(model, params, batch_size=1, max_seq=64,
                         opts=ModelOpts(remat="none"))
    ref = solo.run([mk()])
    srv = BatchedServer(model, params, batch_size=1, max_seq=64,
                        opts=ModelOpts(remat="none"))
    srv.run([Request(rid=0, prompt=[3, 4, 5], max_new_tokens=6)])
    assert srv.run([mk()]) == ref   # second occupancy of the same slot


def test_streaming_api_finish_order_and_bookkeeping(dense):
    model, params = dense
    srv = BatchedServer(model, params, batch_size=2, max_seq=64, opts=OPTS)
    a = Request(rid=0, prompt=[2, 3], max_new_tokens=2)
    b = Request(rid=1, prompt=[4, 5], max_new_tokens=9)
    srv.submit(a), srv.submit(b)
    finished = []
    while srv.queue or any(s is not None for s in srv.active):
        finished.extend(srv.step())
    assert [r.rid for r in finished] == [0, 1]      # streamed as they end
    assert a.done and b.done
    assert a.finished < b.finished
    assert srv.results[0] == a.output


# ---------------------------------------------------------------------------
# Spans in the profiler's trace and the work counters
# ---------------------------------------------------------------------------
STEP_PARTS = ["serve.admit", "serve.dispatch", "serve.sync", "serve.walk"]


def _serve_spans(trace_dir):
    """``(name, start_ns, end_ns, stats)`` of the host's ``serve.*`` events
    in the newest trace under ``trace_dir``, by start."""
    import glob
    import os
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for plane in data.planes for line in plane.lines
           for e in line.events if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: s[1])


@pytest.mark.parametrize("fixture", ("dense", "ssm"))
def test_step_spans_nest_in_order_and_index_steps(fixture, request,
                                                  tmp_path):
    """With the profiler on, each decode step is one ``serve.step`` holding
    admit, dispatch, sync and walk in that order; its ``step`` stat runs
    consecutively and is the index a request admitted on it records in
    ``started``; its counter stats are the server's counters at entry."""
    model, params = request.getfixturevalue(fixture)
    srv = BatchedServer(model, params, batch_size=2, max_seq=64, opts=OPTS)
    srv.run([Request(rid=-1, prompt=[1, 2], max_new_tokens=2)])   # compile
    reqs = [Request(rid=i, prompt=[3 + i] * (2 + i), max_new_tokens=3)
            for i in range(4)]
    for r in reqs:
        srv.submit(r)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    first, entry, admitted = srv.steps, [], []
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    while srv.queue or any(a is not None for a in srv.active):
        waiting = [r for r in reqs if r.started is None]
        entry.append((srv.slot_steps, srv.prompt_tokens))
        srv.step()
        admitted.append([r for r in waiting if r.started is not None])
    assert srv.step() == []                      # no decode step, no span
    jax.profiler.stop_trace()

    spans = _serve_spans(str(tmp_path))
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == srv.steps - first == len(entry)
    assert [s[3]["step"] for s in steps] == list(range(first, srv.steps))
    assert [(s[3]["slot_steps"], s[3]["prompt_tokens"])
            for s in steps] == entry
    resets = 0
    for (_, a, b, stats), new in zip(steps, admitted):
        inner = [s for s in spans if s[0] != "serve.step"
                 and a <= s[1] and s[2] <= b]
        parts = [s for s in inner if s[0] in STEP_PARTS]
        assert [s[0] for s in parts] == STEP_PARTS
        assert all(x[2] <= y[1] for x, y in zip(parts, parts[1:]))
        assert all(r.started == stats["step"] for r in new)
        admit = parts[0]
        nested = [s for s in inner if s[0] == "serve.reset"]
        assert all(admit[1] <= s[1] and s[2] <= admit[2] for s in nested)
        assert len(nested) == (len(new) if fixture == "ssm" else 0)
        resets += len(nested)
    assert sum(map(len, admitted)) == len(reqs)
    assert resets == (len(reqs) if fixture == "ssm" else 0)


def test_work_counters_match_request_lengths(dense):
    """``slot_steps`` and ``prompt_tokens`` equal what the requests' lengths
    give, across slot reuse and a request truncated at ``max_seq``."""
    model, params = dense
    S = 24
    srv = BatchedServer(model, params, batch_size=2, max_seq=S, opts=OPTS)
    reqs = [Request(rid=0, prompt=[5, 6, 7, 8, 9], max_new_tokens=100),
            Request(rid=1, prompt=[9, 10], max_new_tokens=4),
            Request(rid=2, prompt=[1, 2, 3, 4], max_new_tokens=3),
            Request(rid=3, prompt=[11], max_new_tokens=6),
            Request(rid=4, prompt=[12, 13, 14], max_new_tokens=2)]
    out = srv.run(reqs)
    assert len(out[0]) == S - len(reqs[0].prompt)      # truncated
    assert [len(out[i]) for i in range(1, 5)] == [4, 3, 6, 2]
    # a request occupies its slot for its prompt, then for each output
    # token but the last, whose step makes it and frees the slot
    assert srv.slot_steps == sum(len(r.prompt) + len(r.output) - 1
                                 for r in reqs)
    assert srv.slot_steps == sum(r.finished - r.started for r in reqs)
    assert srv.prompt_tokens == sum(len(r.prompt) for r in reqs)
    assert srv.slot_steps < 2 * srv.steps               # slots sat empty


def test_finished_slot_returns_to_position_zero(dense):
    """A slot frees at position 0, so the decode kernel reads one block for
    it rather than its last occupant's length; the neighbour decoding
    beside it is unaffected."""
    model, params = dense
    srv = BatchedServer(model, params, batch_size=2, max_seq=64, opts=OPTS)
    a = Request(rid=0, prompt=[2, 3], max_new_tokens=2)
    b = Request(rid=1, prompt=[4, 5, 6], max_new_tokens=6)
    srv.submit(a), srv.submit(b)
    while not a.done:
        srv.step()
    assert srv.active[0] is None and srv._pos.tolist() == [0, 3]
    out = srv.drain()
    assert srv._pos.tolist() == [0, 0]
    solo = BatchedServer(model, params, batch_size=2, max_seq=64, opts=OPTS)
    assert out[1] == solo.run([Request(rid=1, prompt=[4, 5, 6],
                                       max_new_tokens=6)])[1]


def test_kv_block_counters_follow_dispatched_positions(dense, monkeypatch,
                                                       tmp_path):
    """With the kernel on, ``kv_blocks`` grows each step by ceil((pos + 1)
    / bk) over every slot at the positions dispatched, and
    ``kv_blocks_full`` by B * S / bk, with bk the kernel's own for the
    cache; each ``serve.step`` carries both as they stood at entry.  At
    16-position blocks the greedy tokens are the reference path's."""
    from repro.kernels import decode_attention as kernel
    monkeypatch.setattr(kernel, "MIN_BLOCK_BYTES", 1)    # bk 16 here
    model, params = dense
    B, S = 3, 48
    mk = lambda: [Request(rid=0, prompt=[5, 6, 7], max_new_tokens=60),  # noqa: E731
                  Request(rid=1, prompt=[9, 10], max_new_tokens=20),
                  Request(rid=2, prompt=[1, 2, 3, 4], max_new_tokens=3),
                  Request(rid=3, prompt=[11], max_new_tokens=30)]
    srv = BatchedServer(model, params, batch_size=B, max_seq=S, opts=OPTS,
                        use_kernel=True)
    assert srv._bk == 16
    dispatched, decode = [], srv._decode

    def spy(p, t, pos, c):
        # a copy: on the CPU ``pos`` may share the server's own array
        dispatched.append(np.array(pos))
        return decode(p, t, pos, c)

    srv._decode = spy
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    out = srv.run(mk())
    jax.profiler.stop_trace()

    blocks = [int(np.ceil((pos + 1) / 16).sum()) for pos in dispatched]
    assert max(blocks) > B                  # a slot beyond its first block
    assert srv.kv_blocks == sum(blocks)
    assert srv.kv_blocks_full == len(dispatched) * B * (S // 16)
    steps = [s[3] for s in _serve_spans(str(tmp_path))
             if s[0] == "serve.step"]
    assert [s["kv_blocks"] for s in steps] == np.cumsum([0] + blocks[:-1]
                                                        ).tolist()
    assert [s["kv_blocks_full"] for s in steps] == \
        [i * B * (S // 16) for i in range(len(dispatched))]
    ref = BatchedServer(model, params, batch_size=B, max_seq=S, opts=OPTS,
                        use_kernel=False)
    assert out == ref.run(mk())
    assert ref.kv_blocks == ref.kv_blocks_full == 0     # no kernel


def test_fallback_family_serves_via_lockstep():
    model, params = _model("zamba2-7b")       # hybrid: no per-slot path
    srv = BatchedServer(model, params, batch_size=2, max_seq=64,
                        opts=ModelOpts(attn_chunk=32, remat="none"))
    assert not srv.continuous
    with pytest.raises(RuntimeError, match="lockstep fallback"):
        srv.submit(_reqs(1)[0])
    assert len(srv.run(_reqs(2))) == 2


# ---------------------------------------------------------------------------
# Config router: tell plumbing + outage-mid-serve
# ---------------------------------------------------------------------------
def _register(router, ds, w, budget=12, seed=0, method="random"):
    drv = get_method(method).make_driver(ds.domain, budget, seed,
                                         target="cost")
    router.register(w, drv, binding=bind_objective(
        "offline", workload=w, target="cost", dataset_seed=int(ds.seed)))
    return drv


def test_router_observed_latency_reaches_driver(ds):
    router = ConfigRouter()
    w = ds.workloads[0]
    drv = _register(router, ds, w)
    d = router.route(w)
    assert d.kind == "explore"
    router.observe(d, 0.125)
    # a completed ask batch is told to the driver verbatim
    if len(drv.history):
        assert drv.history.values[-1] == 0.125
    else:                           # batch > 1: finish the round
        while True:
            d = router.route(w)
            if d.kind != "explore":
                break
            router.observe(d, 0.125)
        assert 0.125 in drv.history.values
    assert router.stats(w)["observed"] >= 1


def test_router_serves_incumbent_after_budget(ds):
    router = ConfigRouter()
    w = ds.workloads[0]
    task = ds.task(w, "cost")
    drv = _register(router, ds, w, budget=6)
    while True:
        d = router.route(w)
        if d.kind != "explore":
            break
        router.observe(d, task.objective(d.provider, d.config))
    assert drv.done
    assert d.kind == "exploit"
    best = router.best(w)
    assert best is not None
    assert task.objective(*best) == min(drv.history.values)


def test_router_outage_mid_serve_never_aborts(ds):
    """The fig5 outage scenario replayed through the serving control
    plane: the dead provider is never routed to while down, the outage
    lands as structured failure tells, and service continues."""
    overlay = get_overlay(0, 40, 0.0, "outage:aws:0:20")
    clock = MarketClock()
    router = ConfigRouter(overlay=overlay, clock=clock)
    w = ds.workloads[1]
    task = ds.task(w, "cost")
    drv = _register(router, ds, w, budget=30, method="cb_rbfopt")
    served = []
    for _ in range(25):
        d = router.route(w)
        served.append(d)
        router.observe(d, task.objective(d.provider, d.config))
    assert all(d.provider != "aws" for d in served if d.tick < 20)
    assert drv.failures             # the outage was felt as data...
    assert len(served) == 25        # ...never as an abort
    stats = router.stats(w)
    assert stats["failovers"] >= len(drv.failures)
    assert stats["told"] == len(drv.history)


def test_router_observe_rejects_junk(ds):
    router = ConfigRouter()
    w = ds.workloads[0]
    _register(router, ds, w)
    d = router.route(w)
    with pytest.raises(ValueError, match="finite"):
        router.observe(d, float("nan"))
    router.observe(d, EvalFailure(reason="backend died"))  # allowed
    with pytest.raises(KeyError, match="no driver registered"):
        router.route("no-such-workload")


# ---------------------------------------------------------------------------
# Deprecation shims: warn, but reproduce the new path exactly
# ---------------------------------------------------------------------------
def test_engine_factory_shims_warn_and_match(ds, tmp_path):
    new = experiment_engine(dataset=ds, store_path=str(tmp_path / "a.jsonl"))
    with pytest.warns(DeprecationWarning, match="make_engine"):
        old = make_engine(ds, store_path=str(tmp_path / "b.jsonl"))
    assert old.context == new.context
    with pytest.warns(DeprecationWarning, match="make_objective_engine"):
        old2 = make_objective_engine(context={"dataset_seed": ds.seed})
    assert old2.context == {"dataset_seed": ds.seed}
    for eng in (new, old):          # both paths must actually run units
        drv = get_method("random").make_driver(ds.domain, 3, 0)
        binding = bind_objective("offline", workload=ds.workloads[0],
                                 target="cost", dataset_seed=int(ds.seed))
        (hist,) = drive_units(eng, [(drv, binding)])
        assert len(hist) == 3
    assert old.store.path != new.store.path     # wiring preserved


def test_drive_units_triple_shim_warns_and_matches(ds):
    w, t = ds.workloads[0], "cost"
    engine = experiment_engine(dataset=ds)
    pair_drv = get_method("random").make_driver(ds.domain, 5, 0, target=t)
    binding = bind_objective("offline", workload=w, target=t,
                             dataset_seed=int(ds.seed))
    (pair_hist,) = drive_units(engine, [(pair_drv, binding)])

    triple_drv = get_method("random").make_driver(ds.domain, 5, 0, target=t)
    with pytest.warns(DeprecationWarning, match="triples are deprecated"):
        (triple_hist,) = drive_units(engine, [(triple_drv, w, t)])
    assert triple_hist.points == pair_hist.points
    assert triple_hist.values == pair_hist.values


def test_pair_form_emits_no_deprecation_warning(ds):
    engine = experiment_engine(dataset=ds)
    drv = get_method("random").make_driver(ds.domain, 3, 0, target="cost")
    binding = bind_objective("offline", workload=ds.workloads[0],
                             target="cost", dataset_seed=int(ds.seed))
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        drive_units(engine, [(drv, binding)])
