import os

if __name__ == "__main__":                      # pragma: no cover
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The guarded env-set above MUST stay the very first statement (before any
other import, including ``repro.*``): jax locks the device count on
first init, and only the dry-run *process* is allowed to see 512
placeholder devices.  The ``__main__`` guard keeps a mere import of this
module (tests, the objective registry) from contaminating the importing
process's environment — only the CLI entry point flips the flag, and
every caller invokes it as a subprocess.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-4b \
        --shape train_4k [--multi-pod] [--strategy fsdp_tp] [--out out.json]
"""
import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402

from repro.analysis.roofline import roofline_from_compiled   # noqa: E402
from repro.configs import get_config, get_shape, shapes_for  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_chip_count  # noqa: E402
from repro.launch.steps import build_plan, default_attn_chunk  # noqa: E402
from repro.models.blocks import ModelOpts                    # noqa: E402


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             strategy: str = "fsdp_tp", opts: ModelOpts = None,
             verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    for s, reason in shapes_for(cfg):
        if s.name == shape_name and reason is not None:
            return {"arch": arch, "shape": shape_name,
                    "mesh": "multipod" if multi_pod else "pod",
                    "skipped": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chip_count(mesh)
    plan = build_plan(cfg, shape, mesh, strategy=strategy, opts=opts)

    t0 = time.time()
    with mesh:
        jitted = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                         donate_argnums=plan.donate)
        lowered = jitted.lower(*plan.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    report = roofline_from_compiled(
        compiled, cfg=cfg, shape=shape,
        mesh_name="multipod" if multi_pod else "pod", chips=chips)
    result = report.to_dict()
    result.update({
        "strategy": strategy,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
    })
    if verbose:
        # diagnostics go to stderr: stdout belongs to --out/JSON piping
        err = sys.stderr
        print(f"== {arch} × {shape_name} × "
              f"{'multipod(2,16,16)' if multi_pod else 'pod(16,16)'} "
              f"[{strategy}] ==", file=err)
        print(mem, file=err)
        from repro.analysis.hlo_cost import HloCostAnalysis
        c = HloCostAnalysis(compiled.as_text()).entry_cost()
        top = sorted(c.bytes_by_op.items(), key=lambda kv: -kv[1])[:8]
        print("bytes_by_op:", {k: f"{v:.2e}" for k, v in top}, file=err)
        ca = compiled.cost_analysis()
        print({k: v for k, v in ca.items()
               if k in ("flops", "bytes accessed")}, file=err)
        print(json.dumps(
            {k: result[k] for k in
             ("t_compute", "t_memory", "t_collective", "bottleneck",
              "roofline_fraction", "useful_flops_fraction",
              "peak_memory_per_chip")}, indent=2), file=err)
    return result


def opts_from_cli(args) -> "ModelOpts | None":
    """ModelOpts for the explicitly-set CLI flags, or ``None`` when every
    flag is at its default (``build_plan`` then applies its own per-arch
    defaulting).  The ``--attn-chunk 0`` sentinel resolves to the same
    per-arch default even when another flag forces an opts object — it
    must never silently become a flat 512."""
    if not (args.attn_chunk or args.ce_chunk != 1024
            or args.remat != "full" or args.banded_local):
        return None
    attn = args.attn_chunk or default_attn_chunk(get_config(args.arch))
    return ModelOpts(attn_chunk=attn, ce_chunk=args.ce_chunk,
                     remat=args.remat, banded_local=args.banded_local)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--strategy", default="fsdp_tp")
    ap.add_argument("--attn-chunk", type=int, default=0,
                    help="0 = per-arch default")
    ap.add_argument("--ce-chunk", type=int, default=1024)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--banded-local", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    result = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                      strategy=args.strategy, opts=opts_from_cli(args))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
    if "skipped" in result:
        print(f"SKIPPED: {result['skipped']}", file=sys.stderr)
        sys.exit(0)


if __name__ == "__main__":
    main()
