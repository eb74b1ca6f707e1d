"""Benchmark orchestrator — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV on stdout (and *only* CSV —
error diagnostics go to stderr).  Figure benchmarks run through the
experiment engine: completed work units are replayed from the JSONL
store under results/expstore/, so re-runs and crash-resumes recompute
nothing; ``--workers N`` fans the missing units over a process pool.
``--quick`` subsamples workloads (used for smoke runs); the full
protocol (all 30 workloads) is the default.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import traceback


def main() -> None:
    from repro.exp import add_engine_args
    from repro.exp.cli import ENGINE_ARG_NAMES

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    add_engine_args(ap, granularity=True)
    args, _ = ap.parse_known_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (fig2_sota, fig3_hierarchical, fig4_savings,
                            fig5_drift, fig6_fidelity, fig7_serve,
                            fig8_sched, kernels, roofline, surrogates,
                            table2_dataset)
    modules = [table2_dataset, fig2_sota, fig3_hierarchical, fig4_savings,
               fig5_drift, fig6_fidelity, fig7_serve, fig8_sched,
               surrogates, roofline, kernels]
    print("name,us_per_call,derived")
    ok = True
    for mod in modules:
        name = mod.__name__.split(".")[-1]
        if args.only and args.only not in name:
            continue
        kwargs = {"quick": args.quick}
        accepted = inspect.signature(mod.main).parameters
        for opt in ENGINE_ARG_NAMES + ("granularity",):
            if opt in accepted:
                kwargs[opt] = getattr(args, opt)
        try:
            mod.main(**kwargs)
        except Exception:
            ok = False
            # keep stdout machine-readable: diagnostics belong on stderr
            print(f"{name}.ERROR,,failed", file=sys.stderr)
            traceback.print_exc(file=sys.stderr)
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
