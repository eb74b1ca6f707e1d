#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator JAX finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, settings and metric readers are
found by name (see ``cellspec``).  The run makes the weights on the device
from the seed, builds ``BatchedServer`` and warms it up (set-up), serves
the seeded traffic for ``--seconds`` through ``submit``/``step`` (the
window), keeps serving until what the window started is done, then checks
a seeded sample of the finished requests against the plain float32
reference.  ``--trace 1`` traces part of the window and reports the
per-layer metrics; ``--trace 0`` reports the end-to-end metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared`` (each number compared, with its
limit).  The last lines of standard error repeat what was compared.  With
no TPU, or fewer chips than the cell asks for, the run exits nonzero and
prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import cellspec  # noqa: E402

EXIT_NO_DEVICE = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devices)}")
    return devices


def load_family(name: str):
    path = os.path.join(BENCH, "families", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_family_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    or else at the checkout's fixed ``.jax_cache`` (the program's
    ``enable_compile_cache``), for every program, however small."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compiles (and persistent-cache loads) as they happen."""

    def __init__(self):
        from jax._src import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __call__(self) -> int:
        return self.n


class Tracer:
    """Starts the profiler at ``start`` s into the window and stops it
    ``length`` s later, into a temporary directory."""

    def __init__(self, start: float, length: float):
        self.start, self.stop = start, start + length
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.on = self.off = None

    def tick(self, now: float) -> None:
        import jax
        if self.on is None and self.start <= now < self.stop:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.on = now
        elif self.on is not None and self.off is None and now >= self.stop:
            jax.profiler.stop_trace()
            self.off = now

    def close(self, now: float) -> None:
        if self.on is not None and self.off is None:
            import jax
            jax.profiler.stop_trace()
            self.off = now


class Run:
    """What a metric reader sees of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def build(cell, seed: int):
    """Weights, server and warm-up: everything set-up does."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ArchConfig
    from repro.models.blocks import ModelOpts
    from repro.models.model import build_model
    from repro.runtime.serve import BatchedServer, Request
    import weights as W

    t0 = time.perf_counter()
    family = load_family(cell.config["bench_family"])
    c = family.normalize(cell.config)
    arch = ArchConfig(**family.program_config(c, cell.config_name))
    model = build_model(arch)
    dtype = jnp.dtype(c["dtype"])
    w = jax.block_until_ready(W.make(family.layout(c), seed, dtype))
    t_weights = time.perf_counter() - t0
    want = W.flat(model.abstract_params(dtype))
    if W.flat(w) != want:
        raise ValueError("the benchmark's weight layout differs from the "
                         "model's parameter tree")
    s = cell.settings
    server = BatchedServer(model, w, batch_size=s["batch"],
                           max_seq=s["max_seq"],
                           opts=ModelOpts(remat="none"),
                           use_kernel=s["use_kernel"])
    # warm-up: two short requests through every slot, so that the decode
    # step, the argmax and each slot's admission compile before the window
    for i in range(2 * s["batch"]):
        server.submit(Request(rid=-1 - i, prompt=[i % c["token_vocab"]] * 2,
                              max_new_tokens=2))
    server.drain()
    jax.block_until_ready(server.cache)
    log(f"bench: set-up started {t0 - PROCESS_START:.3f} s after process "
        f"start; weights {t_weights:.3f} s; server and warm-up "
        f"{time.perf_counter() - t0 - t_weights:.3f} s")
    return family, c, w, server, Request


def serve(cell, seed: int, seconds: float, trace: bool, server, Request,
          token_vocab: int, counter):
    import loadgen
    from driver import Client

    s = cell.settings
    planned = loadgen.plan(cell.mix, rate=s.get("rate_rps", 0.0),
                           seconds=seconds, seed=seed, vocab=token_vocab)
    make = lambda p: Request(rid=p.index, prompt=list(p.prompt),  # noqa: E731
                             max_new_tokens=p.max_new_tokens)
    tracer = None
    if trace:
        # the window's last seconds: past the ramp, and the trace's write
        # at stop falls after the window
        length = min(s["trace_seconds"], seconds)
        tracer = Tracer(seconds - length, length)
    client = Client(server, make, annotate=trace)
    window = client.run(
        planned, seconds, drain_cap_s=s["drain_cap_s"],
        min_finished=s["sample_requests"],
        withdraw_at_close=loadgen.arrivals(cell.mix).WITHDRAW_AT_CLOSE,
        on_tick=tracer.tick if tracer else None, compile_counter=counter)
    if tracer:
        tracer.close(seconds)
        window.trace_interval = (tracer.on, tracer.off)
    return window, tracer


def check(cell, family, c, w, window, seed: int, control=None) -> dict:
    """Reference comparison of a seeded sample of finished requests; with
    ``control`` (see ``correctness.CONTROLS``) also the control's reading."""
    import correctness as C
    s = cell.settings
    finished = [C.Served(list(r.planned.prompt), list(r.request.output))
                for r in window.records if r.finished is not None]
    picked = C.sample(finished, s["sample_requests"], seed)
    out = {"requests": len(picked), "tokens": 0, "widest_gap": None,
           "served_off_argmax": 0}
    if picked:
        g = C.gaps(family, w, c, picked, s["max_seq"], control=control)
        out.update(tokens=int(g["served"].size),
                   widest_gap=float(g["served"].max()),
                   served_off_argmax=int((g["served"] > 0).sum()))
        if control:
            out.update(control_widest_gap=float(g["control"].max()),
                       control_off_argmax=int((g["control"] > 0).sum()))
    limit = s["limits"]
    out["correct"] = C.verdict(out["widest_gap"], limit["widest_gap"],
                               out["tokens"], limit["min_tokens"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cellspec.load_cell(args.workload)
    metrics = cell.per_layer if args.trace else cell.end_to_end
    readers = cellspec.readers_for(metrics)
    try:
        devices = require_devices(cell.chips)
    except NoDevice as e:
        log(f"bench: {e}; nothing was run")
        return EXIT_NO_DEVICE
    dev = devices[0]
    cache_dir = use_compile_cache()
    counter = CompileCounter()
    import jax
    log(f"bench: {cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={dev.platform}/{dev.device_kind}"
        f" x{len(devices)} compile cache {cache_dir}")

    family, c, w, server, Request = build(cell, args.seed)
    setup_s = time.perf_counter() - PROCESS_START
    log(f"bench: set-up {setup_s:.3f} s ({counter()} compiles)")
    kv_bytes = {k: v.dtype.itemsize for k, v in server.cache.items()}

    window, tracer = serve(cell, args.seed, args.seconds, bool(args.trace),
                           server, Request, c["token_vocab"], counter)
    import loadgen
    log(f"bench: {window.compiles_in_window} compiles in the window; "
        f"client late by p99 {loadgen.p99_ms(window.lateness):.3f} ms; "
        f"{len(window.steps)} steps; drained at {window.drained_at:.2f} s")
    stats = dev.memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])

    trace = None
    if tracer:
        import tracereduce
        trace = tracereduce.load(tracer.dir)
        shutil.rmtree(tracer.dir, ignore_errors=True)

    server.cache = None        # free the server's state before the reference
    del server
    verdict = check(cell, family, c, w, window, args.seed)

    due = window.due()
    failed = sum(1 for r in due if r.first_token is None)
    run = Run(cell=cell, c=c, family=family, window=window, trace=trace,
              setup_s=setup_s, peaks=peaks_for(dev.device_kind),
              kv_bytes=kv_bytes, chips=cell.chips, seed=args.seed)
    values = {}
    for m in metrics:
        v = readers[m.name](run, m)
        if v is not None:
            values[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(verdict["correct"] and failed == 0),
              "attempted": len(due), "failed": failed,
              "metrics": values, "device": device}
    if trace:
        import tracereduce
        device["busy_s"] = tracereduce.busy_s(trace, cell.chips)
        device["window_s"] = trace.window_s
        result["breakdown"] = tracereduce.breakdown(trace)
    lim = cell.settings["limits"]
    result["compared"] = {
        "widest_gap": {"value": verdict["widest_gap"],
                       "limit": lim["widest_gap"]},
        "tokens_compared": {"value": verdict["tokens"],
                            "limit": lim["min_tokens"]},
        "failed_requests": {"value": failed, "limit": 0},
    }
    log(f"bench: reference sample {verdict['requests']} requests, "
        f"{verdict['served_off_argmax']} served tokens off the reference's "
        f"argmax; bytes in use {stats.get('bytes_in_use', 0)}")
    for k, v in result["compared"].items():
        log(f"compared {k} {v['value']} limit {v['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
