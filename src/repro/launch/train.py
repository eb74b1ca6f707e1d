"""Training launcher.

Small-scale (CPU-runnable) launcher for any ``--arch``: reduced or full
config, auto-resume, checkpointing.  On a real pod the same entry point is
used with ``--mesh data,model`` sizes matching the slice and per-host data
sharding from ``SyntheticLMData.host_shard``.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b \
        --reduced --steps 100 --batch 8 --seq 128 --out runs/qwen
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.configs import get_config
from repro.data.pipeline import SyntheticLMData
from repro.launch.compile_cache import enable_compile_cache
from repro.models.blocks import ModelOpts
from repro.models.model import build_model
from repro.runtime.train_loop import TrainLoop, TrainLoopConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--out", default="runs/train")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    data = SyntheticLMData(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed, family=cfg.family, frame_dim=cfg.frame_dim,
        n_image_tokens=cfg.n_image_tokens, d_model=cfg.d_model)
    loop = TrainLoop(
        model, data,
        TrainLoopConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                        out_dir=args.out, seed=args.seed,
                        compress_grads=args.compress_grads),
        opts=ModelOpts(attn_chunk=min(128, args.seq), ce_chunk=128,
                       remat="none"))
    result = loop.run(jax.random.PRNGKey(args.seed))
    losses = result["losses"]
    print(json.dumps({
        "arch": cfg.name, "steps": result["final_step"],
        "loss_first10": sum(losses[:10]) / max(len(losses[:10]), 1),
        "loss_last10": sum(losses[-10:]) / max(len(losses[-10:]), 1),
    }, indent=2))


if __name__ == "__main__":
    main()
