"""Serving loops: continuous batching with a retained lockstep reference.

``BatchedServer`` is a continuous-batching greedy server: every slot
carries its own position and KV-cache occupancy, requests are admitted
mid-flight via the ``submit()/step()/drain()`` streaming API, and the
flash-decode Pallas kernel (``repro.kernels.ops.decode_attention``) can
run the generation path with per-slot ``length`` instead of a shared
position.  ``run()`` stays as a thin closed-batch compat wrapper.

``LockstepServer`` retains the original loop — one shared ``pos``, a
closed-batch ``run()``, hard truncation at ``S-1`` — as the bit-identity
reference: on closed batches without slot reuse every slot consumes one
token per step, so the per-slot positions coincide with the shared
position and the continuous server's greedy outputs are bit-identical.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.distrib.logical import NOSHARD
from repro.kernels.decode_attention import blocks_read
from repro.models.attention import refuse_windowed_kernel
from repro.models.blocks import ModelOpts
from repro.models.model import Model


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # step-clock bookkeeping (set by the continuous server; units = decode
    # steps, which are wall-clock-independent and therefore deterministic)
    arrived: Optional[int] = None      # submit() time
    started: Optional[int] = None      # slot admission time
    finished: Optional[int] = None     # completion time


class LockstepServer:
    """Original lockstep loop (shared position) — bit-identity reference.

    All slots advance one shared ``pos`` together; the whole batch hard-
    truncates when it reaches ``S-1``.  Late-admitted requests inherit the
    current shared position, so only batches without slot reuse are served
    at correct positions — exactly the regime the continuous server's
    ``run()`` is pinned bit-identical against.
    """

    def __init__(self, model: Model, params, *, batch_size: int = 4,
                 max_seq: int = 256, opts: ModelOpts = ModelOpts(),
                 eos_id: Optional[int] = None):
        self.model = model
        self.params = params
        self.B = batch_size
        self.S = max_seq
        self.opts = opts
        self.eos_id = eos_id
        self.cache = model.init_cache(batch_size, max_seq, jnp.float32)
        self.pos = 0                       # shared position (lockstep batch)
        self._decode = jax.jit(
            lambda p, b, c: model.decode_step(p, b, c, NOSHARD, opts))

    def reset(self) -> None:
        """Rewind for a fresh closed batch (epoch serving)."""
        self.pos = 0
        self.cache = self.model.init_cache(self.B, self.S, jnp.float32)

    def run(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Serve a closed batch of requests to completion (greedy)."""
        queue = list(requests)
        active: List[Optional[Request]] = [None] * self.B
        results: Dict[int, List[int]] = {}
        cursor = np.zeros(self.B, np.int64)      # per-slot prompt cursor
        token = np.zeros((self.B, 1), np.int32)

        def admit():
            for i in range(self.B):
                if active[i] is None and queue:
                    r = queue.pop(0)
                    active[i] = r
                    cursor[i] = 0
                    token[i, 0] = r.prompt[0]

        admit()
        while any(a is not None for a in active) or queue:
            logits, self.cache = self._decode(
                self.params,
                {"token": jnp.asarray(token),
                 "pos": jnp.asarray(self.pos, jnp.int32)},
                self.cache)
            nxt = np.asarray(jnp.argmax(logits, -1))
            self.pos += 1
            for i in range(self.B):
                r = active[i]
                if r is None:
                    continue
                cursor[i] += 1
                if cursor[i] < len(r.prompt):
                    token[i, 0] = r.prompt[cursor[i]]    # prompt feeding
                else:
                    t = int(nxt[i])
                    r.output.append(t)
                    token[i, 0] = t
                    if len(r.output) >= r.max_new_tokens or \
                            (self.eos_id is not None and t == self.eos_id):
                        results[r.rid] = list(r.output)
                        active[i] = None
            if self.pos >= self.S - 1:
                for i in range(self.B):
                    if active[i] is not None:
                        results[active[i].rid] = list(active[i].output)
                        active[i] = None
                break
            admit()
        return results


class BatchedServer:
    """Continuous-batching greedy server with per-slot positions.

    Streaming API: ``submit(request)`` enqueues, ``step()`` admits queued
    requests into free slots and runs ONE fused batched decode step
    (returning the requests that finished on it), ``drain()`` steps until
    the queue and all slots are empty.  A slot frees the moment its
    request finishes — the next queued request is admitted at position 0
    on the very next step, while its co-batched neighbours keep decoding
    at their own positions.

    ``run()`` is a closed-batch compat wrapper; on batches without slot
    reuse its greedy outputs are bit-identical to :class:`LockstepServer`
    (the per-slot mask rows and rope positions coincide with the shared
    position, and the argmax over identical logits is deterministic).

    ``use_kernel=True`` puts the Pallas kernels on the decode step: the
    flash-decode kernel with per-slot ``length`` and, for an MoE model,
    the ``expert_ffn`` kernel (greedy tokens are validated against the jnp
    path).  This keyword, not ``opts.use_kernel``, decides the server's
    decode step.  Only dense/moe configs without a sliding window can take
    it; any other config refuses it with ``ValueError``.
    The decode step donates the KV cache, so one copy is live per step.
    Families with per-slot support: dense / moe (KV caches) and ssm
    (position-free recurrent state, reset per slot on admission);
    hybrid / vlm fall back to an internal lockstep server (``run()`` only).

    Counters, cumulative since construction and readable by an operator:
    ``steps`` (decode steps run), ``slot_steps`` (occupied slots summed
    over those steps: the batch's useful width) and ``prompt_tokens`` (the
    slot-steps whose input token came from a prompt rather than from the
    slot's last output).  ``prompt_tokens / slot_steps`` is the share of
    the decode steps' work spent feeding prompts one token at a time.  An
    MoE model also counts, over the occupied slots' tokens and every layer,
    ``routed_rows`` ((token, expert) pairs routed), ``held_rows`` (those
    that landed on an expert held here) and ``expert_loads`` (the (layer,
    held expert) pairs that took at least one row); the decode step
    returns each slot's held-expert bits, pulled with the argmax.  With
    the kernel on, ``kv_blocks`` counts the KV blocks ``decode_attention``
    reads in each layer, ``ceil((pos + 1) / bk)`` for every slot at the
    position dispatched (a free slot sits at position 0: one block), and
    ``kv_blocks_full`` the ``B * S / bk`` a kernel reading every position
    would; both are computed on the host from the positions.

    Every ``step()`` that runs a decode step is traced with
    ``jax.profiler.TraceAnnotation`` spans, which cost under a microsecond
    each on a TPU v5e host when no profiler is running: ``serve.step``
    around the call, with the stats ``step`` (``steps`` at entry, the
    index ``Request.started`` and ``.finished`` hold), ``slot_steps`` and
    ``prompt_tokens`` as they stand at entry, for an MoE model
    ``routed_rows``, ``held_rows`` and ``expert_loads``, and with the
    kernel on ``kv_blocks`` and ``kv_blocks_full``; inside it, in order,
    ``serve.admit`` (``serve.reset`` around an ssm slot's state reset),
    ``serve.dispatch`` (the host-to-device copies, the decode step and the
    argmax, all dispatched asynchronously), ``serve.sync`` (the host
    blocked until the argmax is back) and ``serve.walk`` (the per-slot
    bookkeeping).  In a trace viewer, ``serve.sync`` set against the
    device's ``jit_decode_step`` and ``jit__argmax`` runs shows how long
    the host waits past the device's work, and ``serve.reset`` shows what
    a slot's state reset costs the host at admission.
    """

    SLOT_FAMILIES = ("dense", "moe", "ssm")

    def __init__(self, model: Model, params, *, batch_size: int = 4,
                 max_seq: int = 256, opts: ModelOpts = ModelOpts(),
                 eos_id: Optional[int] = None,
                 use_kernel: bool = False):
        self.model = model
        self.params = params
        self.B = batch_size
        self.S = max_seq
        self.opts = opts
        self.eos_id = eos_id
        cfg = model.cfg
        self.continuous = cfg.family in self.SLOT_FAMILIES
        if use_kernel:
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"{cfg.name}: the decode kernels serve the dense/moe "
                    "families; use use_kernel=False")
            refuse_windowed_kernel(cfg)
        self.use_kernel = use_kernel
        dopts = dataclasses.replace(opts, use_kernel=use_kernel)
        self._lockstep: Optional[LockstepServer] = None
        if not self.continuous:
            self._lockstep = LockstepServer(
                model, params, batch_size=batch_size, max_seq=max_seq,
                opts=dopts, eos_id=eos_id)
            return
        self.cache = model.init_cache(batch_size, max_seq, jnp.float32)
        self.steps = 0                     # completed decode steps
        self.slot_steps = 0                # occupied slots, summed over steps
        self.prompt_tokens = 0             # slot-steps fed a prompt token
        self.routes = cfg.family == "moe"
        self.routed_rows = 0               # (token, expert) pairs routed
        self.held_rows = 0                 # ... that landed on held experts
        self.expert_loads = 0              # (layer, held expert) pairs used
        self.kv_blocks = 0                 # KV blocks the kernel reads
        self.kv_blocks_full = 0            # ... if it read every position
        self.queue: List[Request] = []
        self.active: List[Optional[Request]] = [None] * self.B
        self.results: Dict[int, List[int]] = {}
        self._cursor = np.zeros(self.B, np.int64)   # per-slot prompt cursor
        self._token = np.zeros((self.B, 1), np.int32)
        self._pos = np.zeros(self.B, np.int32)      # per-slot position
        if use_kernel:                     # the kernel's block size here
            *_, self._bk = blocks_read(self._pos + 1, self.cache["k"])

        def decode_step(p, t, pos, c):
            return model.decode_step(p, {"token": t, "pos": pos}, c,
                                     NOSHARD, dopts, with_stats=self.routes)

        self._decode = jax.jit(decode_step, donate_argnums=3)

    # ------------------------------------------------------------------
    # Streaming API
    # ------------------------------------------------------------------
    def submit(self, request: Request) -> None:
        """Enqueue a request; it is admitted on the next free slot."""
        if not self.continuous:
            raise RuntimeError(
                f"{self.model.cfg.family} serves via the lockstep fallback; "
                "use run()")
        if request.arrived is None:
            request.arrived = self.steps
        self.queue.append(request)

    def step(self) -> List[Request]:
        """Admit queued requests, run one fused decode step.

        Returns the requests that finished on this step (streamed out in
        slot order).  A no-op (empty list) when nothing is queued/active.
        """
        if not self.continuous:
            raise RuntimeError(
                f"{self.model.cfg.family} serves via the lockstep fallback; "
                "use run()")
        if not self.queue and not any(a is not None for a in self.active):
            return []
        stats = dict(step=self.steps, slot_steps=self.slot_steps,
                     prompt_tokens=self.prompt_tokens)
        if self.routes:
            stats.update(routed_rows=self.routed_rows,
                         held_rows=self.held_rows,
                         expert_loads=self.expert_loads)
        if self.use_kernel:
            stats.update(kv_blocks=self.kv_blocks,
                         kv_blocks_full=self.kv_blocks_full)
        with TraceAnnotation("serve.step", **stats):
            with TraceAnnotation("serve.admit"):
                self._admit()
            occupied = []
            for i, r in enumerate(self.active):
                if r is not None:
                    occupied.append(i)
                    self.prompt_tokens += int(self._cursor[i] < len(r.prompt))
            self.slot_steps += len(occupied)
            if self.use_kernel:
                read, full, _ = blocks_read(self._pos + 1, self.cache["k"])
                self.kv_blocks += read
                self.kv_blocks_full += full
            with TraceAnnotation("serve.dispatch"):
                logits, self.cache, *counts = self._decode(
                    self.params, jnp.asarray(self._token),
                    jnp.asarray(self._pos, jnp.int32), self.cache)
                nxt = jnp.argmax(logits, -1)
            with TraceAnnotation("serve.sync"):
                if self.routes:
                    nxt, held = jax.device_get((nxt, counts[0]["held"]))
                else:
                    nxt = np.asarray(nxt)
            self.steps += 1
            with TraceAnnotation("serve.walk"):
                if self.routes:
                    self._count_routes(held[occupied])
                return self._walk(nxt)

    def drain(self) -> Dict[int, List[int]]:
        """Step until every queued/active request has finished."""
        if not self.continuous:
            raise RuntimeError(
                f"{self.model.cfg.family} serves via the lockstep fallback; "
                "use run()")
        out: Dict[int, List[int]] = {}
        while any(a is not None for a in self.active) or self.queue:
            for r in self.step():
                out[r.rid] = list(r.output)
        return out

    def run(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Closed-batch compat wrapper: submit everything, drain."""
        if not self.continuous:
            return self._lockstep.run(requests)
        for r in requests:
            self.submit(r)
        return self.drain()

    # ------------------------------------------------------------------
    # Internals
    # ------------------------------------------------------------------
    def _admit(self) -> None:
        for i in range(self.B):
            if self.active[i] is None and self.queue:
                r = self.queue.pop(0)
                self.active[i] = r
                self._cursor[i] = 0
                self._pos[i] = 0
                self._token[i, 0] = r.prompt[0]
                r.started = self.steps
                self._reset_slot(i)

    def _count_routes(self, held: np.ndarray) -> None:
        """Add one step's routes: ``held``, the occupied slots' (n, L)
        held-expert bits."""
        self.routed_rows += held.size * self.model.cfg.top_k
        self.held_rows += int(np.bitwise_count(held).sum())
        self.expert_loads += int(np.bitwise_count(
            np.bitwise_or.reduce(held, axis=0)).sum())

    def _reset_slot(self, i: int) -> None:
        if self.model.cfg.family != "ssm":
            # KV entries above/at the slot's position are masked out and
            # overwritten as it advances — no reset needed.
            return
        # recurrent state carries across occupants: re-zero the slot
        with TraceAnnotation("serve.reset"):
            self.cache = {k: v.at[:, i].set(0)
                          for k, v in self.cache.items()}

    def _walk(self, nxt: np.ndarray) -> List[Request]:
        """Advance every occupied slot past the step that produced ``nxt``;
        return the requests that finished on it."""
        finished: List[Request] = []
        for i in range(self.B):
            r = self.active[i]
            if r is None:
                continue
            self._pos[i] += 1
            self._cursor[i] += 1
            if self._cursor[i] < len(r.prompt):
                self._token[i, 0] = r.prompt[self._cursor[i]]  # prompt feed
            else:
                t = int(nxt[i])
                r.output.append(t)
                self._token[i, 0] = t
                if len(r.output) >= r.max_new_tokens or \
                        (self.eos_id is not None and t == self.eos_id):
                    self._finish(i, finished)
                    continue
            if self._pos[i] >= self.S - 1:
                # this slot's KV budget is exhausted: truncate ONLY this
                # request (the lockstep loop flushed the whole batch here)
                self._finish(i, finished)
        return finished

    def _finish(self, i: int, finished: List[Request]) -> None:
        r = self.active[i]
        r.done = True
        r.finished = self.steps
        self.results[r.rid] = list(r.output)
        self.active[i] = None
        self._pos[i] = 0       # a free slot attends to one position only
        finished.append(r)
