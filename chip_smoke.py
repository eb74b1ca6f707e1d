#!/usr/bin/env python3
"""Bring-up smoke test: the serving path on a TPU at published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips (one host, 2x2)

One chip runs two phases:

- serve: qwen1.5-4b (40 layers, d_model 2560, vocab 151936) with random
  bf16 weights behind ``BatchedServer(use_kernel=True)``, driven through
  ``submit``/``step``/``drain``.  It checks that every request finishes with
  tokens in ``[0, vocab)``, that the compiled decode step holds the Pallas
  kernel (``tpu_custom_call``), and that one decode step's logits from the
  kernel path match the ``use_kernel=False`` path from the same cache state.
- ssd: mamba2-130m at published widths through ``Model.forward`` with the
  ``ssd_scan`` kernel against the reference scan.

``--chips 4`` runs one phase only: gemma-7b's ``tp_serve`` decode plan on a
``(data=1, model=4)`` mesh with sharded parameters, and the same plan cut to
2 layers run sharded on the mesh against unsharded on one chip.

Every phase raises on failure.  Where JAX finds no TPU the script exits
nonzero before any work.  The last line printed is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
All data is made from ``--seed``.  Wall times printed here are smoke
figures, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

KERNEL_MARK = "tpu_custom_call"
#: A path under test must match its reference to within SPREAD_FACTOR times
#: the reference's own precision spread: the relative L2 distance between
#: the reference run at JAX's default matmul precision and at "highest".
#: With bf16 weights and activations that spread grows with depth (on a
#: v5e: 2.3e-2 on qwen1.5-4b's 40-layer logits, 5.7e-2 on mamba2-130m's
#: 24-layer hidden state), and a fixed bound would be either loose for a
#: shallow model or tight for a deep one.  MIN_TOL floors the bound where
#: both precisions agree.
SPREAD_FACTOR = 2.0
MIN_TOL = 1e-3
#: sharded vs one-chip logits of the 2-layer plan: the partitioned bf16
#: matmuls round partial sums before they are reduced across chips, a noise
#: the matmul precision does not reach (1.0e-2 on four v5e chips at
#: gemma-7b's widths, 4.8e-3 on four virtual CPU devices at reduced widths)
MESH_TOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(a, b) -> tuple:
    """(relative L2 error, max abs error) of ``a`` against reference ``b``."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        raise AssertionError("non-finite values")
    return (float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)),
            float(np.abs(a - b).max()))


def spread_tol(name: str, ref, ref_default) -> float:
    """Bound for a path checked against ``ref`` (the reference at "highest"
    matmul precision), from the reference's own spread to ``ref_default``."""
    spread, _ = rel_err(ref_default, ref)
    log(f"{name}: reference spread (default vs highest precision) "
        f"rel_l2={spread:.3e}")
    return max(SPREAD_FACTOR * spread, MIN_TOL)


def check_close(name: str, got, ref, tol: float) -> None:
    rel, mx = rel_err(got, ref)
    agree = float(np.mean(np.argmax(got, -1) == np.argmax(ref, -1)))
    log(f"{name}: rel_l2={rel:.3e} max_abs={mx:.3e} argmax_agree={agree:.2f}"
        f" tol(rel_l2)={tol:.3e}")
    if not rel <= tol:
        raise AssertionError(f"{name}: rel_l2 {rel:.3e} > {tol:.3e}")


def highest(fn, *args):
    """``fn(*args)`` traced and run at the "highest" matmul precision."""
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def assert_kernel_in(name: str, hlo: str) -> None:
    n = hlo.count(KERNEL_MARK)
    log(f"{name}: {KERNEL_MARK} x{n} in the compiled HLO")
    if not n:
        raise AssertionError(f"{name}: no Pallas kernel in the compiled HLO")


def device_bytes() -> str:
    out = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        out.append(f"{d.id}:{st.get('bytes_in_use', 0) / 2**30:.2f}/"
                   f"{st.get('peak_bytes_in_use', 0) / 2**30:.2f}GiB")
    return " ".join(out)


def describe(cfg) -> str:
    return (f"{cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
            f"n_heads={cfg.n_heads} n_kv_heads={cfg.n_kv_heads} "
            f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab} "
            f"dtype={cfg.dtype}")


# ---------------------------------------------------------------------------
# one chip: serving through BatchedServer with the decode kernel
# ---------------------------------------------------------------------------
def serve_phase(cfg, *, seed: int, batch: int = 4, max_seq: int = 512,
                n_requests: int = 16, prompt_len=(16, 128),
                new_tokens: int = 32, compare_after: int = 100) -> None:
    from repro.distrib.logical import NOSHARD
    from repro.models.blocks import ModelOpts
    from repro.models.model import build_model
    from repro.runtime.serve import BatchedServer, Request

    log(f"serve: {describe(cfg)}")
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        model.init(jax.random.PRNGKey(seed), jnp.dtype(cfg.dtype)))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"serve: params {n_bytes / 1e9:.2f} GB in {cfg.dtype}, "
        f"init {time.perf_counter() - t0:.1f}s; devices {device_bytes()}")

    opts = ModelOpts(remat="none")
    server = BatchedServer(model, params, batch_size=batch, max_seq=max_seq,
                           opts=opts, use_kernel=True)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(
                        0, cfg.vocab,
                        int(rng.integers(prompt_len[0], prompt_len[1] + 1))
                    ).tolist(),
                    max_new_tokens=new_tokens)
            for i in range(n_requests)]
    log(f"serve: batch={batch} max_seq={max_seq} requests={n_requests} "
        f"prompts {min(len(r.prompt) for r in reqs)}-"
        f"{max(len(r.prompt) for r in reqs)} tokens, {new_tokens} new each")

    t0 = time.perf_counter()
    hlo = server._decode.lower(
        params, jnp.asarray(server._token), jnp.asarray(server._pos),
        server.cache).compile().as_text()
    log(f"serve: decode step compiled in {time.perf_counter() - t0:.1f}s")
    assert_kernel_in("serve decode step", hlo)

    for r in reqs:
        server.submit(r)
    t0 = time.perf_counter()
    for _ in range(compare_after):
        server.step()
    wall = time.perf_counter() - t0

    # one decode step from the live cache state, kernel vs reference path
    ref_decode = jax.jit(lambda p, t, pos, c: model.decode_step(
        p, {"token": t, "pos": pos}, c, NOSHARD,
        dataclasses.replace(opts, use_kernel=False))[0])
    args = (params, jnp.asarray(server._token),
            jnp.asarray(server._pos, jnp.int32))
    log(f"serve: comparing logits at slot positions {server._pos.tolist()}")
    ref = np.asarray(highest(ref_decode, *args, server.cache))
    ref_default = np.asarray(ref_decode(*args, server.cache))
    ker, _ = server._decode(*args, jax.tree.map(jnp.copy, server.cache))
    check_close("serve logits kernel vs reference", np.asarray(ker), ref,
                spread_tol("serve logits", ref, ref_default))

    t0 = time.perf_counter()
    server.drain()
    wall += time.perf_counter() - t0
    done = sum(r.done for r in reqs)
    if done != n_requests or len(server.results) != n_requests:
        raise AssertionError(f"serve: {done}/{n_requests} requests finished")
    tokens = [t for r in reqs for t in r.output]
    if any(len(r.output) != new_tokens for r in reqs):
        raise AssertionError("serve: a request stopped short of its tokens")
    if not all(0 <= t < cfg.vocab for t in tokens):
        raise AssertionError("serve: token outside [0, vocab)")
    log(f"serve: {done}/{n_requests} requests finished, {len(tokens)} tokens "
        f"in [0, {cfg.vocab}), {server.steps} decode steps; "
        f"devices {device_bytes()}")
    log(f"serve smoke figure (not a benchmark metric): {wall:.2f}s wall for "
        f"{server.steps} steps (first-step compile included), "
        f"{len(tokens) / wall:.1f} generated tokens/s")


# ---------------------------------------------------------------------------
# one chip: the ssd_scan kernel inside Model.forward
# ---------------------------------------------------------------------------
def ssd_phase(cfg, *, seed: int, batch: int = 2, seq: int = 2048) -> None:
    from repro.distrib.logical import NOSHARD
    from repro.models.blocks import ModelOpts
    from repro.models.model import build_model

    log(f"ssd: {describe(cfg)} ssm_heads={cfg.ssm_heads} "
        f"ssm_head_dim={cfg.ssm_head_dim} ssm_state={cfg.ssm_state} "
        f"chunk={cfg.ssm_chunk}; batch={batch} seq={seq}")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed), jnp.dtype(cfg.dtype))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, seq),
                                0, cfg.vocab, jnp.int32)

    def fwd(use_kernel):
        opts = ModelOpts(remat="none", use_kernel=use_kernel)
        return jax.jit(lambda p, t: model.forward(
            p, {"tokens": t}, NOSHARD, opts)[0])

    ker = fwd(True)
    t0 = time.perf_counter()
    assert_kernel_in("ssd forward",
                     ker.lower(params, tokens).compile().as_text())
    h_ker = np.asarray(ker(params, tokens), np.float32)
    if h_ker.shape != (batch, seq, cfg.d_model):
        raise AssertionError(f"ssd: hidden shape {h_ker.shape}")
    ref_fn = fwd(False)
    ref = np.asarray(highest(ref_fn, params, tokens), np.float32)
    ref_default = np.asarray(ref_fn(params, tokens), np.float32)
    check_close("ssd hidden kernel vs reference", h_ker, ref,
                spread_tol("ssd hidden", ref, ref_default))
    log(f"ssd: done in {time.perf_counter() - t0:.1f}s")


# ---------------------------------------------------------------------------
# four chips: a sharded tp_serve decode plan
# ---------------------------------------------------------------------------
def _init_params(model, plan, seed):
    """Parameters created directly in the plan's shardings."""
    dtype = jnp.dtype(model.cfg.dtype)
    return jax.jit(lambda k: model.init(k, dtype),
                   out_shardings=plan.in_shardings[0])(
        jax.random.PRNGKey(seed))


def _decode_steps(model, plan, shape, params, tokens):
    """Teacher-forced decode steps from an empty cache created in the
    plan's sharding -> (logits (steps, B, V), last greedy tokens)."""
    cache = jax.jit(lambda: model.init_cache(
        shape.global_batch, shape.seq_len, jnp.bfloat16),
        out_shardings=plan.in_shardings[2])()
    step = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                   donate_argnums=plan.donate)
    out = []
    for i in range(tokens.shape[1]):
        nxt, logits, cache = step(
            params, {"token": tokens[:, i:i + 1],
                     "pos": jnp.asarray(i, jnp.int32)}, cache)
        out.append(np.asarray(logits))
    return np.stack(out), np.asarray(nxt)


def mesh_phase(cfg, *, seed: int, n_chips: int = 4, batch: int = 8,
               seq: int = 1024, steps: int = 4) -> None:
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_plan
    from repro.models.model import build_model

    shape = ShapeSpec("smoke_decode", "decode", seq, batch)
    mesh = make_mesh(1, n_chips)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (batch, steps),
                                0, cfg.vocab, jnp.int32)

    log(f"mesh: {describe(cfg)}; tp_serve decode on (data=1, model={n_chips})"
        f" batch={batch} kv_seq={seq} steps={steps}")
    t0 = time.perf_counter()
    with mesh:
        model = build_model(cfg)
        plan = build_plan(cfg, shape, mesh, strategy="tp_serve")
        params = _init_params(model, plan, seed)
        big = max(jax.tree.leaves(params), key=lambda x: x.size)
        n_dev = len(big.sharding.device_set)
        shard = big.addressable_shards[0].data.shape
        log(f"mesh: sharded params in {time.perf_counter() - t0:.1f}s;"
            f" largest leaf {big.shape} on {n_dev} devices, shard {shard};"
            f" devices {device_bytes()}")
        if n_dev != n_chips or shard == big.shape:
            raise AssertionError("mesh: parameters are not sharded")
        logits, nxt = _decode_steps(model, plan, shape, params, tokens)
    if logits.shape != (steps, batch, cfg.vocab) or \
            not np.isfinite(logits).all():
        raise AssertionError(f"mesh: bad logits {logits.shape}")
    if not ((0 <= nxt) & (nxt < cfg.vocab)).all():
        raise AssertionError("mesh: token outside [0, vocab)")
    log(f"mesh: {steps} decode steps finite, tokens in [0, {cfg.vocab}), "
        f"{time.perf_counter() - t0:.1f}s incl. compile; "
        f"devices {device_bytes()}")
    del params

    # the same plan cut to 2 layers: sharded on the mesh vs one chip
    cut = dataclasses.replace(cfg, n_layers=2)
    model = build_model(cut)
    one = make_mesh(1, 1, devices=jax.devices()[:1])
    with one:
        plan1 = build_plan(cut, shape, one, strategy="tp_serve")
        params1 = _init_params(model, plan1, seed)
        ref, _ = _decode_steps(model, plan1, shape, params1, tokens)
    with mesh:
        plan4 = build_plan(cut, shape, mesh, strategy="tp_serve")
        params4 = jax.device_put(params1, plan4.in_shardings[0])
        del params1
        got, _ = _decode_steps(model, plan4, shape, params4, tokens)
    check_close(f"mesh 2-layer logits over {steps} steps, sharded vs one "
                "chip", got, ref, MESH_TOL)
    log(f"mesh: devices {device_bytes()}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded gemma-7b phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform}); "
                 "nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)}")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(get_config("gemma-7b"), seed=args.seed, n_chips=4)
    else:
        serve_phase(get_config("qwen1.5-4b"), seed=args.seed)
        ssd_phase(get_config("mamba2-130m"), seed=args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
