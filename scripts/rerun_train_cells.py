#!/usr/bin/env python
"""§Perf iteration 1: re-measure the train_4k cells after the bf16
weight pre-cast (serving cells already used bf16 parameters, so only the
training path changes).  Writes results/dryrun_precast/."""
import json
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(ROOT, "src"))
from repro.configs import ARCH_IDS    # noqa: E402

OUT = os.path.join(ROOT, "results", "dryrun_precast")


def main():
    from repro.configs import REGISTRY
    os.makedirs(OUT, exist_ok=True)
    jobs = []
    for arch in ARCH_IDS:
        jobs.append((arch, "fsdp_tp", f"{arch}.train_4k.pod.json"))
        if REGISTRY[arch].n_experts == 0:
            jobs.append((arch, "fsdp_dp",
                         f"{arch}.train_4k.pod.fsdp_dp.json"))
    for arch, strategy, name in jobs:
        out = os.path.join(OUT, name)
        if os.path.exists(out):
            continue
        # the dry-run compiles for placeholder CPU devices: keep it off
        # any chip this machine holds
        env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
                   JAX_PLATFORMS="cpu")
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", "train_4k", "--out", out,
               "--strategy", strategy]
        print("RUN", arch, strategy, flush=True)
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=2400, env=env)
        if r.returncode != 0:
            print("FAIL", arch, r.stderr[-1500:], flush=True)
        else:
            d = json.load(open(out))
            print(f"  t={d['t_step']:.2f}s coll={d['t_collective']:.2f}s "
                  f"mem={d['t_memory']:.2f}s roof={d['roofline_fraction']:.4f}",
                  flush=True)


if __name__ == "__main__":
    main()
