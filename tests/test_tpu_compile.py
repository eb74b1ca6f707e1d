"""The Pallas kernels compile for a TPU v5e at real widths, without a chip.

Each test lowers a kernel with ``interpret=False`` against one chip of a
described (not attached) ``v5e:2x2`` topology and asserts that the compiled
program holds the Mosaic kernel (``tpu_custom_call``).  What the chip's
compiler refuses (unaligned blocks, too much VMEM) fails here; interpret
mode accepts it.  Nothing runs, so nothing here checks results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and it keeps it until it exits.
"""
import ast
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import REGISTRY
from repro.kernels import ops as kernel_ops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _bench_kernel_pattern(reader: str = "decode_attention_roofline") -> str:
    """``KERNEL``, the pattern by which the benchmark's ``reader`` finds its
    kernel among a trace's ops (read from its source, which imports modules
    only the benchmark's path holds)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "metrics", f"{reader}.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and \
                [t.id for t in node.targets] == ["KERNEL"]:
            return ast.literal_eval(node.value)
    raise AssertionError(f"no KERNEL in {path}")


def _compiled_text(fn, args, sharding) -> str:
    sds = [jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
           for shape, dt in args]
    return jax.jit(fn).lower(*sds).compile().as_text()


# (B, Hq, Hkv, S, D, cache dtype, precise), each at the block size the
# kernel derives from these shapes, as served
DECODE_CASES = {
    # qwen1.5-4b behind BatchedServer: 20 heads, MHA, f32 cache
    "qwen1.5-4b": (4, 20, 20, 2048, 128, jnp.float32, False),
    # the benchmark's cells: qwen1.5-4b chat and summarize
    "qwen1.5-4b.chat": (8, 20, 20, 512, 128, jnp.float32, False),
    "qwen1.5-4b.summarize": (4, 20, 20, 1024, 128, jnp.float32, False),
    # phi3.5-moe chat: GQA 4:1, float32 activations over bf16 weights
    "phi3.5-moe.chat": (64, 32, 8, 512, 128, jnp.float32, True),
    # minitron-8b heads: 32 query heads over 8 KV heads (G=4)
    "minitron-8b-gqa": (4, 32, 8, 2048, 128, jnp.bfloat16, False),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_attention_compiles(one_chip, case):
    B, Hq, Hkv, S, D, dt, precise = DECODE_CASES[case]
    hlo = _compiled_text(
        lambda q, k, v, n: decode_attention(q, k, v, n, precise=precise,
                                            interpret=False),
        [((B, Hq, D), dt), ((B, Hkv, S, D), dt), ((B, Hkv, S, D), dt),
         ((B,), jnp.int32)], one_chip)
    assert "tpu_custom_call" in hlo
    # a trace's op names are the instructions' text, as the benchmark sees it
    kernel = re.compile(_bench_kernel_pattern())
    ops = [ln.strip().removeprefix("ROOT ") for ln in hlo.splitlines()]
    assert any(kernel.search(op) and "tpu_custom_call" in op for op in ops)


# The served decode step may hold the cache, or anything as large as one
# layer's K slab, only as these: no copy, transpose, slice or fusion of it.
IN_PLACE_OPS = {"parameter", "get-tuple-element", "tuple", "while",
                "dynamic-update-slice"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = (.+?) ([a-z][a-z0-9-]*)\(")
_F32 = re.compile(r"f32\[([0-9,]*)\]")


def _cache_sized(hlo: str, elements: int):
    """``(name, opcode)`` of each instruction, in any computation of the
    compiled text, whose result holds a float32 array of ``elements`` or
    more (the cache's dtype; the bf16 weights are larger and are not
    looked at)."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and any(math.prod(int(d) for d in dims.split(",") if d)
                     >= elements for dims in _F32.findall(m.group(2))):
            out.append((m.group(1), m.group(3)))
    return out


# the benchmark's cells: (batch, max_seq) of qwen1.5-4b.chat / .summarize
SERVED = {"chat": (8, 512), "summarize": (4, 1024)}


@pytest.mark.parametrize("cell", sorted(SERVED))
def test_served_decode_step_reads_and_writes_cache_in_place(
        one_chip, monkeypatch, cell):
    """``BatchedServer``'s jitted decode step at qwen1.5-4b widths (depth
    cut to 2 layers), kernel on, per-slot positions, float32 cache: the
    cache and its layer slabs appear only as parameters, loop plumbing and
    in-place row writes, and the kernel is still found by the benchmark's
    pattern."""
    from repro.models.blocks import ModelOpts
    from repro.models.model import build_model
    from repro.runtime.serve import BatchedServer
    # the described chip is not the default backend: lower the kernel for
    # it rather than for the interpreter
    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    B, S = SERVED[cell]
    cfg = dataclasses.replace(REGISTRY["qwen1.5-4b"], n_layers=2)
    model = build_model(cfg)
    srv = BatchedServer(model, None, batch_size=B, max_seq=S,
                        opts=ModelOpts(remat="none"), use_kernel=True)
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                             sharding=one_chip)
    hlo = srv._decode.lower(
        jax.tree.map(on_chip, model.abstract_params(jnp.bfloat16)),
        jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip),
        jax.tree.map(on_chip, srv.cache)).compile().as_text()
    slab = B * cfg.n_kv_heads * S * cfg.head_dim
    big = _cache_sized(hlo, slab)
    assert [x for x in big if x[1] not in IN_PLACE_OPS] == []
    assert sum(op == "dynamic-update-slice" for _, op in big) == 2 * B
    kernel = re.compile(_bench_kernel_pattern())
    ops = [ln.strip().removeprefix("ROOT ") for ln in hlo.splitlines()]
    assert any(kernel.search(op) and "tpu_custom_call" in op for op in ops)


def _served_step_text(model, B, S, one_chip) -> str:
    """The compiled text of ``BatchedServer``'s jitted decode step for
    ``model``, kernels on, per-slot positions, float32 cache."""
    from repro.models.blocks import ModelOpts
    from repro.runtime.serve import BatchedServer
    srv = BatchedServer(model, None, batch_size=B, max_seq=S,
                        opts=ModelOpts(remat="none"), use_kernel=True)
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                             sharding=one_chip)
    return srv._decode.lower(
        jax.tree.map(on_chip, model.abstract_params(jnp.bfloat16)),
        jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip),
        jax.tree.map(on_chip, srv.cache)).compile().as_text()


def _instructions(hlo: str):
    return [m for m in map(_INSTR.match, hlo.splitlines()) if m]


# the served step in each benchmark cell at 2 layers: (arch, batch,
# max_seq, held experts) and its instruction lines as compiled; the kernel
# path these cells run does not change with code that only other paths use
SERVED_STEPS = {
    "chat": ("qwen1.5-4b", 8, 512, None, 699),
    "summarize": ("qwen1.5-4b", 4, 1024, None, 632),
    "phi3.5-moe.chat": ("phi3.5-moe-42b-a6.6b", 64, 512, (0, 8), 2136),
}


@pytest.mark.parametrize("cell", sorted(SERVED_STEPS))
def test_served_qwen_decode_step_unchanged(one_chip, monkeypatch, cell):
    """The served decode step of each benchmark cell, qwen1.5-4b's and
    Phi-3.5-MoE's, compiles to the instruction count pinned for it."""
    from repro.models.model import build_model
    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    arch, B, S, held, instructions = SERVED_STEPS[cell]
    cfg = dataclasses.replace(REGISTRY[arch], n_layers=2)
    if held:
        cfg = dataclasses.replace(cfg, held_experts=held)
    hlo = _served_step_text(build_model(cfg), B, S, one_chip)
    assert len(_instructions(hlo)) == instructions


def test_served_phi_moe_decode_step_compiles(one_chip, monkeypatch):
    """``BatchedServer``'s decode step at the Phi-3.5-MoE cell's shapes
    (B=64, max_seq 512, published widths, 8 of 16 experts held; depth cut
    to 2 layers): both kernels are found by the benchmark's patterns, and
    no instruction holds as much as one layer's held experts except as a
    parameter or loop plumbing: the expert kernel reads them in place."""
    from repro.models.model import build_model
    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    cfg = dataclasses.replace(REGISTRY["phi3.5-moe-42b-a6.6b"], n_layers=2,
                              held_experts=(0, 8))
    hlo = _served_step_text(build_model(cfg), 64, 512, one_chip)
    ops = [ln.strip().removeprefix("ROOT ") for ln in hlo.splitlines()]
    for reader in ("expert_ffn_roofline", "decode_attention_roofline"):
        kernel = re.compile(_bench_kernel_pattern(reader))
        assert any(kernel.search(op) and "tpu_custom_call" in op
                   for op in ops), reader
    layer_experts = 8 * 3 * cfg.d_model * cfg.d_ff
    big = [(m.group(1), m.group(3)) for m in _instructions(hlo)
           if any(math.prod(int(d) for d in dims.split(",") if d)
                  >= layer_experts // 3
                  for dims in re.findall(r"bf16\[([0-9,]*)\]",
                                         m.group(2)))]
    assert big and [x for x in big if x[1] not in IN_PLACE_OPS] == []


def test_flash_attention_compiles(one_chip):
    shape = (1, 8, 2048, 128)
    hlo = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True, bq=128,
                                        bk=128, interpret=False),
        [(shape, jnp.bfloat16)] * 3, one_chip)
    assert "tpu_custom_call" in hlo


def test_ssd_scan_compiles(one_chip):
    # mamba2-130m: 24 heads of 64, state 128, chunk 256
    B, L, H, P, N = 2, 2048, 24, 64, 128
    hlo = _compiled_text(
        lambda x, dt, A, Bm, Cm, D: ssd_scan(x, dt, A, Bm, Cm, D, chunk=256,
                                             interpret=False),
        [((B, L, H, P), jnp.bfloat16), ((B, L, H), jnp.float32),
         ((H,), jnp.float32), ((B, L, N), jnp.bfloat16),
         ((B, L, N), jnp.bfloat16), ((H,), jnp.bfloat16)], one_chip)
    assert "tpu_custom_call" in hlo
