"""A copy of the benchmark with tiny cells, for CPU tests.

``make(tmp)`` copies ``bench/`` and ``BENCHMARK.json`` under ``tmp`` (with a
``src`` link to the program) and adds two cells at reduced widths: a dense
decoder with the decode kernel (interpreted on the CPU) and a Mamba2 model,
each under a short Poisson chat mix.  ``load_run(root)`` imports that
copy's ``run.py``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DENSE = {"bench_family": "dense", "num_attention_heads": 4,
         "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
         "vocab_size": 4096, "num_key_value_heads": 4,
         "tie_word_embeddings": False, "rope_theta": 5000000.0,
         "rms_norm_eps": 1e-06, "torch_dtype": "bfloat16"}
SSM = {"bench_family": "ssm", "d_model": 32, "n_layer": 2, "vocab_size": 32000,
       "pad_vocab_size_multiple": 16, "tie_embeddings": True,
       "served_dtype": "bfloat16",
       "ssm_layer": {"d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16,
                     "chunk_size": 256, "norm_epsilon": 1e-05}}
MIX = {"arrivals": "poisson",
       "prompt": {"median": 8, "sigma": 0.5, "min": 3, "max": 20},
       "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 12}}
OFFLINE = {"arrivals": "offline", "requests": 6,
           "prompt": {"median": 12, "sigma": 0.3, "min": 8, "max": 20},
           "output": {"median": 6, "sigma": 0.3, "min": 4, "max": 10}}


#: widest-gap limits of the tiny cells, between what bf16 serving reads
#: and what the fp8 control reads at these widths (tests/bench readings)
LIMITS = {"dense": 0.08, "ssm": 0.3}


def _cell(family, batch, use_kernel, rate=None):
    c = {"batch": batch, "max_seq": 64, "use_kernel": use_kernel,
         "trace_seconds": 1, "drain_cap_s": 30, "sample_requests": 4,
         "limits": {"widest_gap": LIMITS[family], "min_tokens": 8}}
    if rate:
        c["rate_rps"] = rate
    return c


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make(tmp: str) -> str:
    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    b = os.path.join(root, "bench")
    _write(os.path.join(b, "configs", "tiny-dense.json"), DENSE)
    _write(os.path.join(b, "configs", "tiny-ssm.json"), SSM)
    _write(os.path.join(b, "mixes", "tiny-chat.json"), MIX)
    _write(os.path.join(b, "mixes", "tiny-offline.json"), OFFLINE)
    _write(os.path.join(b, "cells", "tiny-dense.chat.json"),
           _cell("dense", 3, True, rate=100.0))
    _write(os.path.join(b, "cells", "tiny-dense.offline.json"),
           _cell("dense", 2, False))
    _write(os.path.join(b, "cells", "tiny-ssm.chat.json"),
           _cell("ssm", 4, False, rate=30.0))
    bench["configs"] += [
        {"name": "tiny-dense", "source": "test", "reduced": [], "why": "test",
         "file": "bench/configs/tiny-dense.json"},
        {"name": "tiny-ssm", "source": "test", "reduced": [], "why": "test",
         "file": "bench/configs/tiny-ssm.json"}]
    tiny = ["tiny-dense.chat", "tiny-ssm.chat", "tiny-dense.offline"]
    bench["workloads"] += [
        {"name": "tiny-dense.chat", "config": "tiny-dense",
         "traffic": "tiny-chat", "chips": 1, "why": "test"},
        {"name": "tiny-ssm.chat", "config": "tiny-ssm",
         "traffic": "tiny-chat", "chips": 1, "why": "test"},
        {"name": "tiny-dense.offline", "config": "tiny-dense",
         "traffic": "tiny-offline", "chips": 1, "why": "test"}]
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name, cells in (("itl_p95_ms", tiny[:2]),
                        ("tokens_per_s", tiny[2:]),
                        ("step_ms.chat", tiny[:2]),
                        ("decode_device_ms.chat", tiny[:2]),
                        ("mfu.chat", tiny[:2]),
                        ("idle_share.chat", tiny[:2]),
                        ("decode_attention_roofline.chat", tiny[:1]),
                        ("step_ms.summarize", tiny[2:])):
        by_name[name]["workloads"] = by_name[name]["workloads"] + cells
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def load_run(root: str):
    """Import ``<root>/bench/run.py`` as a fresh module."""
    for name in [m for m in sys.modules
                 if m in ("cellspec", "loadgen", "driver", "weights",
                          "correctness", "tracereduce")]:
        del sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(root, "bench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
