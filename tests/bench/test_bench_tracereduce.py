"""The reduction from a trace to busy time, idle share, kernel time, self
times and the idle breakdown, on a small trace laid out as a v5e trace is
(nested ``while`` ops, a ``%decode_attention.<n>`` custom-call, host
``bench.*`` spans), and on a real profiler trace taken here on the CPU."""
import jax
import jax.numpy as jnp
import pytest

import tracereduce as T

DEV = "/device:TPU:0"
ATT = ("%decode_attention.4 = f32[8,20,1,128]{3,2,1,0:T(1,128)S(1)} "
       "custom-call(s32[8]{0} %a, f32[8,20,1,128]{3,2,1,0} %b)")
LOOP = "%while.15 = (s32[]{:T(128)}, bf16[8,1,2560]{2,0,1}) while(%t)"
FUS = ("%fusion.133 = bf16[8,6912]{1,0:T(8,128)(2,1)S(1)} "
       "fusion(bf16[40,2560,6912]{2,1,0} %w), kind=kLoop")
ARGMAX = "%iota_reduce_fusion = (bf16[8], s32[8]) fusion(f32[8,151936] %x)"

# two steps of 10 ms windows: ops, then host-only gaps
FIXTURE = {
    "devices": {DEV: [
        [LOOP, 0.001, 0.006],          # step 1: loop 5 ms holding
        [FUS, 0.0015, 0.0035],         #   a fusion 2 ms
        [ATT, 0.004, 0.005],           #   the kernel 1 ms
        [ARGMAX, 0.0065, 0.007],       # argmax 0.5 ms
        [LOOP, 0.011, 0.016],          # step 2, the same
        [FUS, 0.0115, 0.0135],
        [ATT, 0.014, 0.015],
        [ARGMAX, 0.0165, 0.017],
        [ATT, 0.030, 0.031],           # outside the window: ignored
    ]},
    "spans": [["submit", 0.0, 0.0008], ["step", 0.0008, 0.0075],
              ["poll", 0.0075, 0.0095], ["wait", 0.0095, 0.0105],
              ["step", 0.0105, 0.0175], ["poll", 0.0175, 0.020]],
}


@pytest.fixture
def trace():
    return T.from_json(FIXTURE)


def test_window_busy_and_idle(trace):
    assert trace.window == (0.0, 0.020)
    assert T.busy_s(trace, 1) == pytest.approx(0.011)      # 2 x (5 + 0.5)
    assert T.steps_in(trace) == 2


def test_kernel_time_by_name(trace):
    secs, n = T.kernel_s(trace, r"^%decode_attention(\.\d+)? = ")
    assert n == 2 and secs == pytest.approx(0.002)
    assert T.kernel_s(trace, "nothing-like-this") == (0.0, 0)


def test_self_times_subtract_children(trace):
    st = T.self_times(trace)
    assert st["%while.15 = (s32[], bf16[8,1,2560]) while"] == \
        pytest.approx(0.004)
    assert st["%fusion.133 = bf16[8,6912] fusion"] == pytest.approx(0.004)
    assert st["%decode_attention.4 = f32[8,20,1,128] custom-call"] == \
        pytest.approx(0.002)
    assert sum(st.values()) == pytest.approx(T.busy_s(trace, 1))


def test_idle_gaps_by_host_span(trace):
    gaps = dict(T.idle_gaps(trace))
    # idle (ms): [0,1] mostly submit, [6,6.5] step, [7,11] mostly poll,
    # [16,16.5] step, [17,20] mostly poll
    assert gaps == pytest.approx({"poll x2": 0.004 + 0.003,
                                  "step x2": 0.0005 + 0.0005,
                                  "submit x1": 0.001})
    assert sum(gaps.values()) == pytest.approx(0.020 - 0.011)
    b = T.breakdown(trace)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]


def test_json_round_trip(trace):
    again = T.from_json(T.to_json(trace))
    assert again.spans == trace.spans
    assert T.busy_s(again, 1) == T.busy_s(trace, 1)


def test_real_trace_host_spans(tmp_path):
    """A profiler trace taken on the CPU holds the bench.* spans in order;
    it holds no TPU plane, so there is nothing busy to read."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    x = jnp.ones(128)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            x = (x * 2).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.poll"):
            pass
    jax.profiler.stop_trace()
    t = T.load(str(tmp_path))
    assert [s[0] for s in t.spans] == ["step", "poll"] * 3
    assert T.steps_in(t) == 3 and t.window_s > 0
    assert not t.devices and T.busy_s(t, 1) == 0.0


def test_recorded_v5e_trace():
    """Two decode steps of qwen1.5-4b.chat traced on a TPU v5e (op names
    shortened): the decode_attention kernel once per layer and step, nested
    ops inside the layer scan counted once, every idle gap under a span."""
    import gzip
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "qwen_chat_two_steps.json.gz")
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    t = T.from_json(data)
    rec = data["recorded"]
    assert T.steps_in(t) == 2
    secs, n = T.kernel_s(t, r"^%decode_attention(\.\d+)? = ")
    assert n == 2 * 40 and secs == pytest.approx(rec["kernel"][0])
    busy = T.busy_s(t, 1)
    assert busy == pytest.approx(rec["busy_s"])
    assert 0.9 * t.window_s < busy < t.window_s
    assert sum(T.self_times(t).values()) == pytest.approx(busy, rel=1e-6)
    gaps = T.idle_gaps(t)
    assert {g.split(" ")[0] for g, _ in gaps} <= {"step", "poll", "submit"}
    assert sum(s for _, s in gaps) == pytest.approx(t.window_s - busy)
