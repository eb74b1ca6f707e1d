#!/usr/bin/env python3
"""Find a chat cell's knee once, on the chip: serve the cell's mix at each
offered rate in turn and report whether the queue grew over the window.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> \\
        --rates 0.4 0.5 ...

One process, one set-up; between rates the server drains.  For each rate:
requests due, the queue (sent, not yet in a slot) at the window's close,
the 95th percentile of queue wait for requests sent in the first and the
second half of the window, and the end-to-end tails.  The queue grows when
more requests wait at the close than one plus 1% of those due (one may have
arrived since the last step), or the second half waits longer than the
first by more than a step per slot: arrivals outrun the slots.
The knee is the highest rate at which it does not; the cell runs at 0.8
times that.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run  # noqa: E402


def _p95(xs):
    return float(np.percentile(xs, 95)) if len(xs) else float("nan")


def summarize(window, rate: float, batch: int) -> dict:
    w = window.seconds
    due = window.due()
    queued_at_close = sum(1 for r in due if r.sent is not None and
                          (r.admitted_seen is None or r.admitted_seen > w))
    waits = [(r.planned.arrival_s, r.admitted_seen - r.planned.arrival_s)
             for r in due if r.admitted_seen is not None]
    first = [x for t, x in waits if t < w / 2]
    second = [x for t, x in waits if t >= w / 2]
    step_s = float(np.median([s.end - s.start for s in window.steps])) \
        if window.steps else float("nan")
    gaps = [b - a for r in window.records
            for a, b in zip(r.token_times, r.token_times[1:]) if b < w]
    ttft = [r.first_token - r.planned.arrival_s for r in due
            if r.first_token is not None]
    grows = queued_at_close > 1 + 0.01 * len(due) or \
        _p95(second) > _p95(first) + batch * step_s
    return {"rate_rps": rate, "due": len(due),
            "queued_at_close": queued_at_close,
            "wait_p95_first_half_ms": 1e3 * _p95(first),
            "wait_p95_second_half_ms": 1e3 * _p95(second),
            "step_median_ms": 1e3 * step_s,
            "itl_p95_ms": 1e3 * _p95(gaps), "ttft_p95_ms": 1e3 * _p95(ttft),
            "grows": bool(grows)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    cell = bench_run.cellspec.load_cell(args.workload)
    try:
        bench_run.require_devices(cell.chips)
    except bench_run.NoDevice as e:
        bench_run.log(f"sweep: {e}; nothing was run")
        return bench_run.EXIT_NO_DEVICE
    bench_run.use_compile_cache()
    _, c, _, server, Request = bench_run.build(cell, args.seed)
    rows = []
    for i, rate in enumerate(args.rates):
        at_rate = dataclasses.replace(
            cell, settings=dict(cell.settings, rate_rps=rate))
        window, _ = bench_run.serve(at_rate, args.seed + i, args.seconds,
                                    False, server, Request,
                                    c["token_vocab"], None)
        row = summarize(window, rate, cell.settings["batch"])
        print(json.dumps(row), flush=True)
        rows.append(row)
        server.drain()
    sustained = [r["rate_rps"] for r in rows if not r["grows"]]
    knee = max(sustained) if sustained else None
    out = {"workload": args.workload, "seconds": args.seconds,
           "knee_rps": knee, "rows": rows}
    print(json.dumps({"knee_rps": knee}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
