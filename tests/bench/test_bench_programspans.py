"""The program's ``serve.*`` spans and the device's program runs in a
trace, and the metrics that read them: the device's idle time inside and
between program runs, the host's time in dispatch and in admission with
the slot walk, and the share of slot work spent feeding prompts.  On the
trace laid out as a v5e trace is (``test_bench_tracereduce.FIXTURE``) with
the server's spans and the device's program runs added, on the recorded v5e
trace that has neither, on a profiler trace taken here on the CPU, and
through a traced tiny run."""
import copy
import gzip
import json
import os

import jax
import jax.numpy as jnp
import pytest

import programspans as P
import tinybench
import tracereduce as T
from test_bench_tracereduce import DEV, FIXTURE

ms = 1e-3


def _step(at, step, slots, prompt, dispatch, sync_end, end):
    """One ``serve.step`` from ``at`` ms: admit 0.05, dispatch ``dispatch``,
    sync to ``sync_end``, walk to ``end``; counters at entry as stats."""
    d = at + 0.05 + dispatch
    return [["serve.step", at * ms, end * ms,
             {"step": step, "slot_steps": slots, "prompt_tokens": prompt}],
            ["serve.admit", at * ms, (at + 0.05) * ms, {}],
            ["serve.dispatch", (at + 0.05) * ms, d * ms, {}],
            ["serve.sync", d * ms, sync_end * ms, {}],
            ["serve.walk", sync_end * ms, end * ms, {}]]


# device idle (ms): [0,1], [6,6.5], [7,11], [16,16.5], [17,20]; the
# program runs (a run begun before the window, then decode step and argmax
# twice) leave idle inside them: 0.3 of [0,1] in the first run, which the
# window's start cuts, 0.2 of it and 0.1 of [6,6.5] in the decode step,
# 0.1 of [6,6.5] in the argmax; the rest of the idle is between runs.
WITH_PROGRAM = copy.deepcopy(FIXTURE)
WITH_PROGRAM["modules"] = {DEV: [[-0.5 * ms, 0.3 * ms], [0.8 * ms, 6.1 * ms],
                                 [6.4 * ms, 7.0 * ms], [11 * ms, 16 * ms],
                                 [16.5 * ms, 17 * ms], [30 * ms, 31 * ms]]}
# the host's spans need not line up with the device's: the first step
# began before the window (its dispatch outside, its walk inside)
WITH_PROGRAM["program"] = (_step(-0.6, 9, 36, 30, 0.05, 0.5, 0.7) +
                           _step(0.9, 10, 40, 30, 0.3, 7.0, 7.4) +
                           _step(10.7, 11, 48, 33, 0.1, 16.2, 17.4))
INSIDE = (0.3 + 0.2 + 0.1 + 0.1) * ms
BETWEEN = (0.020 - 0.011) - INSIDE


@pytest.fixture(autouse=True)
def _restore_load(monkeypatch):
    """Loading a reader installs the hook: undo it after each test."""
    import tracereduce          # the module ``install`` patches
    monkeypatch.setattr(tracereduce, "load", tracereduce.load)


@pytest.fixture
def traces():
    return T.from_json(FIXTURE), P.from_json(WITH_PROGRAM)


def _run(trace, chips=1):
    return type("Run", (), {"trace": trace, "chips": chips})()


def _reader(name):
    import cellspec
    return cellspec.metric_reader(name)


def test_idle_inside_and_between_programs_add_up_to_window_less_busy(traces):
    _, t = traces
    inside, between = P.idle_split(t, 1)
    assert inside == pytest.approx(INSIDE)
    assert between == pytest.approx(BETWEEN)
    assert inside + between == pytest.approx(t.window_s - T.busy_s(t, 1))
    assert P.decode_steps(t) == 2            # the first began before
    per = P.gap_ms_per_step(t, 1)
    assert per == pytest.approx((INSIDE / 2e-3, BETWEEN / 2e-3))
    assert _reader("op_gap_ms.chat")(_run(t), None) == pytest.approx(per[0])
    assert _reader("program_gap_ms.summarize")(_run(t), None) == \
        pytest.approx(per[1])


def test_idle_split_ignores_the_host_clock(traces):
    """Shifting every host span against the device's clock moves no device
    reading: the idle is placed against the device's own program runs."""
    _, t = traces
    shifted = copy.deepcopy(WITH_PROGRAM)
    for s in shifted["program"]:
        s[1] += 0.3 * ms
        s[2] += 0.3 * ms
    moved = P.from_json(shifted)
    assert P.decode_steps(moved) == P.decode_steps(t)
    assert P.gap_ms_per_step(moved, 1) == P.gap_ms_per_step(t, 1)


def test_host_time_per_decode_step(traces):
    _, t = traces
    # dispatches of steps 10 and 11; admissions and walks that start in
    # the window, step 9's walk among them, over the two decode steps
    assert P.host_ms_per_step(t, ("serve.dispatch",)) == \
        pytest.approx((0.3 + 0.1) / 2)
    walk = (0.2 + 0.05 + 0.4 + 0.05 + 1.2) / 2
    assert P.host_ms_per_step(t, ("serve.admit", "serve.walk")) == \
        pytest.approx(walk)
    assert _reader("dispatch_ms.chat")(_run(t), None) == pytest.approx(0.2)
    assert _reader("admit_walk_ms.summarize")(_run(t), None) == \
        pytest.approx(walk)


def test_prompt_share_is_the_counter_deltas(traces):
    _, t = traces
    # steps 10 and 11 start in the window: (33 - 30) / (48 - 40)
    assert P.prompt_share(t) == pytest.approx(100.0 * 3 / 8)
    assert _reader("prompt_share.summarize")(_run(t), None) == \
        pytest.approx(100.0 * 3 / 8)


def test_existing_reductions_ignore_program_spans(traces):
    """Every reading the benchmark had is the same with the server's spans
    in the trace as without."""
    bare, t = traces
    assert t.spans == bare.spans and t.window == bare.window
    for chips in (1, 2):
        assert T.busy_s(t, chips) == T.busy_s(bare, chips)
    assert T.steps_in(t) == T.steps_in(bare)
    kernel = r"^%decode_attention(\.\d+)? = "
    assert T.kernel_s(t, kernel) == T.kernel_s(bare, kernel)
    assert T.self_times(t) == T.self_times(bare)
    assert T.idle_gaps(t) == T.idle_gaps(bare)
    assert T.breakdown(t) == T.breakdown(bare)
    assert T.to_json(t) == T.to_json(bare)


def test_no_program_spans_read_none(traces):
    """A trace of a program that writes no ``serve.*`` spans, such as the
    recorded v5e trace or the parent's, gives the new readers nothing, not
    an error; so does one without the device's program runs."""
    bare, _ = traces
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "qwen_chat_two_steps.json.gz")
    with gzip.open(path, "rt") as f:
        recorded = P.from_json(json.load(f))
    runs_only = copy.deepcopy(WITH_PROGRAM)
    del runs_only["program"]
    spans_only = copy.deepcopy(WITH_PROGRAM)
    del spans_only["modules"]
    runs_only, spans_only = P.from_json(runs_only), P.from_json(spans_only)
    for t in (bare, recorded, runs_only, None):
        for name in ("program_gap_ms.chat", "op_gap_ms.chat",
                     "dispatch_ms.chat", "admit_walk_ms.summarize",
                     "prompt_share.summarize"):
            assert _reader(name)(_run(t), None) is None
    for name in ("program_gap_ms.chat", "op_gap_ms.chat"):
        assert _reader(name)(_run(spans_only), None) is None
    assert _reader("dispatch_ms.chat")(_run(spans_only), None) is not None
    assert P.idle_split(recorded, 1) is None            # no program runs
    assert P.idle_split(runs_only, 1) is not None


def test_load_keeps_program_spans_once_installed(tmp_path, monkeypatch):
    """On a real profiler trace, ``install`` has ``tracereduce.load`` keep
    the ``serve.*`` spans with their stats, and nothing else changes."""
    import tracereduce
    monkeypatch.setattr(tracereduce, "load", getattr(
        tracereduce.load, "__wrapped__", tracereduce.load))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    x = jnp.ones(128)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            with jax.profiler.TraceAnnotation("serve.step", step=i,
                                              slot_steps=2 * i,
                                              prompt_tokens=i):
                with jax.profiler.TraceAnnotation("serve.sync"):
                    x = (x * 2).block_until_ready()
    jax.profiler.stop_trace()
    bare = tracereduce.load(str(tmp_path))
    P.install()
    P.install()                                 # once is enough
    assert tracereduce.load.__wrapped__.__name__ == "load"
    t = tracereduce.load(str(tmp_path))
    assert not hasattr(bare, "program")
    assert t.spans == bare.spans
    assert t.modules == {}                      # no TPU plane here
    assert [s[0] for s in t.program] == ["serve.step", "serve.sync"] * 3
    assert [s[3] for s in t.program if s[0] == "serve.step"] == [
        {"step": i, "slot_steps": 2 * i, "prompt_tokens": i}
        for i in range(3)]
    assert P.prompt_share(t) == pytest.approx(50.0)


def test_traced_tiny_run_reports_prompt_share(tmp_path, capsys):
    """Through ``bench/run.py``: a traced run of a tiny cell reports the
    prompt share and the dispatch time from the server's own spans; the CPU
    trace has no TPU plane, so the idle readers report nothing.  (The metrics are listed
    for the tiny chat cell here only to be read.)"""
    root = tinybench.make(tmp_path)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in ("prompt_share.summarize", "dispatch_ms.summarize",
                         "program_gap_ms.summarize"):
            m["workloads"] = m["workloads"] + ["tiny-dense.chat"]
    with open(path, "w") as f:
        json.dump(bench, f)
    run = tinybench.load_run(root)
    run.require_devices = lambda chips: jax.devices()
    run.peaks_for = lambda kind: {"bf16_flops_per_s": 1e12,
                                  "hbm_bytes_per_s": 1e11}
    run.use_compile_cache = lambda: "off"
    capsys.readouterr()
    assert run.main(["--workload", "tiny-dense.chat", "--seed", "5",
                     "--seconds", "2", "--trace", "1"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert 0 < res["metrics"]["prompt_share.summarize"]["value"] < 100
    assert res["metrics"]["dispatch_ms.summarize"]["value"] > 0
    assert "program_gap_ms.summarize" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
