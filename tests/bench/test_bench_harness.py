"""The harness end to end at tiny sizes on the CPU: cells, mixes,
configurations and metrics found by name from new files; the result line;
no result without a chip; and ``correct`` false when the timed path is
broken underneath."""
import filecmp
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import tinybench

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tinybench.make(tmp_path_factory.mktemp("bench"))


def _run(checkout, capsys, args, patch=None):
    """``bench/run.py`` of the copy, past its look for a chip; returns the
    parsed result line, or None when it printed none."""
    run = tinybench.load_run(checkout)
    run.require_devices = lambda chips: jax.devices()
    run.peaks_for = lambda kind: PEAKS
    run.use_compile_cache = lambda: "off"   # nothing written by tests
    if patch:
        patch(run)
    capsys.readouterr()
    rc = run.main(args)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1]) if out else None


def test_new_files_are_found_by_name_without_edits(checkout):
    """The tiny configurations, mixes and cells are new files beside the
    real ones; every file the repository has is unchanged in the copy."""
    ours = os.path.join(tinybench.REPO, "bench")
    theirs = os.path.join(checkout, "bench")
    for d, _, files in os.walk(ours):
        if "__pycache__" in d:
            continue
        rel = os.path.relpath(d, ours)
        for f in files:
            if f.endswith(".pyc"):
                continue
            assert filecmp.cmp(os.path.join(d, f),
                               os.path.join(theirs, rel, f), shallow=False)
    import cellspec
    cell = cellspec.load_cell("tiny-ssm.chat",
                              bench_dir=os.path.join(checkout, "bench"))
    assert cell.config["bench_family"] == "ssm"
    assert cell.mix["arrivals"] == "poisson"
    assert {m.name for m in cell.end_to_end} == {"setup_s", "itl_p95_ms"}
    assert "decode_attention_roofline.chat" not in \
        {m.name for m in cell.per_layer}


@pytest.mark.parametrize("cell,e2e", [
    ("tiny-dense.chat", {"setup_s", "itl_p95_ms"}),
    ("tiny-ssm.chat", {"setup_s", "itl_p95_ms"}),
    ("tiny-dense.offline", {"setup_s", "tokens_per_s"})])
def test_tiny_cell_result_line(checkout, capsys, cell, e2e):
    res = _run(checkout, capsys, ["--workload", cell, "--seed",
                                  str(2**31 + 7), "--seconds", "2",
                                  "--trace", "0"])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == e2e
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == len(jax.devices())
    assert list(res)[-1] == "compared"
    assert res["compared"]["widest_gap"]["value"] <= \
        res["compared"]["widest_gap"]["limit"]


def test_new_metric_reader_is_picked_up(tmp_path, capsys):
    """A metric that reads a new counter is one new file and one entry."""
    root = tinybench.make(tmp_path)
    with open(os.path.join(root, "bench", "metrics", "steps_seen.py"),
              "w") as f:
        f.write('"""steps_seen: decode steps the client ran in the '
                'window."""\n\n\ndef read(run, metric):\n'
                '    w = run.window\n'
                '    return sum(1 for s in w.steps if s.start < w.seconds)\n')
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"].append({
        "name": "steps_seen", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "server loop",
        "moves": "itl_p95_ms", "workloads": ["tiny-ssm.chat"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    res = _run(root, capsys, ["--workload", "tiny-ssm.chat", "--seed", "3",
                              "--seconds", "2", "--trace", "1"])
    assert res["metrics"]["steps_seen"]["value"] > 0
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _script(root, args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _script(tinybench.REPO, ["bench/run.py", "--workload",
                                 "qwen1.5-4b.chat", "--seed", "1",
                                 "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    """A directory with only BENCHMARK.json and bench/ has no program: the
    run fails, even past its look for a chip, and prints no result."""
    import shutil
    shutil.copytree(os.path.join(tinybench.REPO, "bench"),
                    tmp_path / "bench")
    shutil.copy(os.path.join(tinybench.REPO, "BENCHMARK.json"), tmp_path)
    code = ("import sys, jax; sys.path.insert(0, 'bench'); import run; "
            "run.require_devices = lambda chips: jax.devices(); "
            "sys.exit(run.main(sys.argv[1:]))")
    p = _script(str(tmp_path), ["-c", code, "--workload", "qwen1.5-4b.chat",
                                "--seed", "1", "--seconds", "1",
                                "--trace", "0"])
    assert p.returncode != 0 and p.stdout.strip() == ""


def _break(kind):
    """Wrap the server's compiled decode step with one fault."""
    def patch(run):
        build = run.build

        def broken_build(cell, seed):
            family, c, w, server, Request = build(cell, seed)
            decode = server._decode
            calls = [0]

            def faulty(p, tok, pos, cache):
                if kind == "state_unchanged":
                    logits, _ = decode(p, tok, pos, jax.tree.map(
                        lambda x: x.copy(), cache))
                    return logits, cache
                logits, cache = decode(p, tok, pos, cache)
                logits = np.array(logits)
                calls[0] += 1
                if kind == "half_batch":
                    half = logits.shape[0] // 2
                    logits[half:] = logits[0]
                elif kind == "token_altered" and calls[0] % 3 == 0:
                    # every third step, in every slot: each request that
                    # makes three tokens or more serves one altered token,
                    # whichever requests the sample draws
                    rows = np.arange(logits.shape[0])
                    logits[rows, logits.argmin(-1)] = logits.max(-1) + 1
                return jax.numpy.asarray(logits), cache

            server._decode = faulty
            return family, c, w, server, Request
        run.build = broken_build
    return patch


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_broken_timed_path_is_not_correct(checkout, capsys, fault):
    res = _run(checkout, capsys, ["--workload", "tiny-dense.chat", "--seed",
                                  "11", "--seconds", "2", "--trace", "0"],
               patch=_break(fault))
    assert res["correct"] is False
    assert res["compared"]["widest_gap"]["value"] > \
        res["compared"]["widest_gap"]["limit"]
