"""``kv_read_share``: the share of the KV cache the decode kernel reads,
from the server's ``kv_blocks`` and ``kv_blocks_full`` counters in the
``serve.step`` spans.  On a made-up trace, on traces of a program that does
not count the blocks, and through a traced tiny run."""
import json
import os

import jax
import pytest

import programspans
import tinybench


@pytest.fixture(autouse=True)
def _restore_load(monkeypatch):
    """Loading a reader installs the hook: undo it after each test."""
    import tracereduce          # the module ``install`` patches
    monkeypatch.setattr(tracereduce, "load", tracereduce.load)


def _reader():
    import cellspec
    return cellspec.metric_reader("kv_read_share.chat")


def _run(trace):
    return type("Run", (), {"trace": trace, "chips": 1})()


def _trace(steps, counts=True, at=0.0):
    """``steps`` serve.step spans from ``at`` s, 10 ms apart, whose stats
    at step i hold i * i blocks read and 32 * i in full."""
    def stats(i):
        s = {"step": i, "slot_steps": 2 * i, "prompt_tokens": i}
        if counts:
            s.update(kv_blocks=i * i, kv_blocks_full=32 * i)
        return s
    return programspans.from_json({
        "devices": {}, "spans": [["bench.step", 0.0, 1.0]],
        "program": [["serve.step", at + 0.01 * i, at + 0.01 * i + 0.005,
                     stats(i)] for i in range(steps)]})


def test_kv_read_share_is_the_counter_deltas():
    # from the first step to the last: 100 blocks read of 320
    assert _reader()(_run(_trace(11)), None) == pytest.approx(31.25)
    # steps that start past the window's end (1 s) are not counted: from
    # 0.955 s, steps 0-4 start inside it, 16 blocks read of 128
    late = _trace(11, at=0.955)
    assert len([s for s in late.program if s[1] < late.window[1]]) == 5
    assert _reader()(_run(late), None) == pytest.approx(12.5)


@pytest.mark.parametrize("trace", ("no_counters", "one_step", "none"))
def test_kv_read_share_reads_nothing_without_counters(trace):
    """A program that does not count the blocks (the parent's), a window
    with one decode step, or no trace at all: nothing to read."""
    t = {"no_counters": _trace(11, counts=False), "one_step": _trace(1),
         "none": None}[trace]
    assert _reader()(_run(t), None) is None


def test_traced_tiny_run_reports_kv_read_share(tmp_path, capsys):
    """Through ``bench/run.py``: the tiny dense cell serves with the kernel
    on, and its traced run reports the share from the server's spans.  At
    these widths one block holds a slot's whole cache, so every slot reads
    it all."""
    root = tinybench.make(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] == "kv_read_share.chat":
            m["workloads"] = m["workloads"] + ["tiny-dense.chat"]
    with open(path, "w") as f:
        json.dump(bench, f)
    run = tinybench.load_run(root)
    run.require_devices = lambda chips: jax.devices()
    run.peaks_for = lambda kind: {"bf16_flops_per_s": 1e12,
                                  "hbm_bytes_per_s": 1e11}
    run.use_compile_cache = lambda: "off"
    capsys.readouterr()
    assert run.main(["--workload", "tiny-dense.chat", "--seed",
                     str(2**31 + 18), "--seconds", "2", "--trace", "1"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["kv_read_share.chat"] == {"value": 100.0,
                                                    "unit": "%"}
