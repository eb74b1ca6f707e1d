"""Serving launcher: batched greedy decoding for any ``--arch``.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m \
        --reduced --requests 8 --new-tokens 16
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.blocks import ModelOpts
from repro.models.model import build_model
from repro.runtime.serve import BatchedServer, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only; no decode path")
    model = build_model(cfg)
    # serve in the config's dtype: float32 masters would double the bytes
    params = model.init(jax.random.PRNGKey(args.seed), jnp.dtype(cfg.dtype))
    rng = np.random.default_rng(args.seed)
    reqs = [
        Request(rid=i,
                prompt=rng.integers(0, cfg.vocab, rng.integers(4, 12)
                                    ).tolist(),
                max_new_tokens=args.new_tokens)
        for i in range(args.requests)
    ]
    server = BatchedServer(model, params, batch_size=args.batch,
                           max_seq=args.max_seq,
                           opts=ModelOpts(attn_chunk=64, remat="none"))
    t0 = time.time()
    results = server.run(reqs)
    dt = time.time() - t0
    total_tokens = sum(len(v) for v in results.values())
    print(json.dumps({
        "arch": cfg.name, "requests": len(results),
        "generated_tokens": total_tokens,
        "tokens_per_s": round(total_tokens / dt, 2),
        "sample_output": results[0][:8],
    }, indent=2))


if __name__ == "__main__":
    main()
