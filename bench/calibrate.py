#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 101 102 ... [--out <file.json>]

For each seed, in one process: the cell's set-up, a window of ``--seconds``
at the cell's own load, the drain, then the comparison of the seeded sample
against the float32 reference (the sound reading) and, at each position of
the same sequences, the fp8 control's first choice against the same
reference (the control's reading).  The benchmark's own runs never run a
control.  Prints one line per seed and a summary: the largest sound reading and the
smallest control reading.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    cell = bench_run.cellspec.load_cell(args.workload)
    try:
        bench_run.require_devices(cell.chips)
    except bench_run.NoDevice as e:
        bench_run.log(f"calibrate: {e}; nothing was run")
        return bench_run.EXIT_NO_DEVICE
    bench_run.use_compile_cache()
    rows = []
    for seed in args.seeds:
        family, c, w, server, Request = bench_run.build(cell, seed)
        window, _ = bench_run.serve(cell, seed, args.seconds, False, server,
                                    Request, c["token_vocab"], None)
        server.cache = None
        del server
        v = bench_run.check(cell, family, c, w, window, seed, control="fp8")
        row = {"seed": seed, "sound": v["widest_gap"], "tokens": v["tokens"],
               "requests": v["requests"],
               "sound_off_argmax": v["served_off_argmax"],
               "fp8": v["control_widest_gap"],
               "fp8_off_argmax": v["control_off_argmax"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        del w, window
        gc.collect()
    summary = {"workload": args.workload, "seconds": args.seconds,
               "sound_max": max(r["sound"] for r in rows if r["sound"]
                                is not None),
               "fp8_min": min(r["fp8"] for r in rows), "rows": rows}
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}),
          flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
