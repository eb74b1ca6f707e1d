"""From a profiler trace to device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
On a TPU v5e (JAX 0.9) a device is a plane named ``/device:TPU:<n>``; its
line ``XLA Ops`` holds one event per HLO operation, nested where an
operation (a ``while`` of the layer scan) contains others, named by the
HLO text (``%fusion.71 = bf16[64,3352]{...} fusion(...)``).  The host's
planes hold the benchmark's ``bench.<span>`` annotations on the same clock.

- The traced window runs from the first ``bench.*`` span to the last.
- Busy time is the union of the ``XLA Ops`` intervals inside the window,
  averaged over the chips used; idle share is one minus busy over window.
- A kernel's time is the summed duration of the ops whose name contains a
  pattern; a device op's self time is its duration less the ops it contains.
- Idle gaps are the stretches of the window outside the busy union, each
  put down to the host span that overlaps it most.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]          # (start, end), seconds


@dataclasses.dataclass
class DeviceEvent:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    """The parts of one trace the readers use; times in seconds."""
    devices: Dict[str, List[DeviceEvent]]   # plane name -> ops, by start
    spans: List[Tuple[str, float, float]]   # host bench spans, by start

    @property
    def window(self) -> Interval:
        return (self.spans[0][1], max(e for _, _, e in self.spans))

    @property
    def window_s(self) -> float:
        a, b = self.window
        return b - a


def load(trace_dir: str) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices: Dict[str, List[DeviceEvent]] = {}
    spans = []
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PREFIX):
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        DeviceEvent(e.name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events)
            else:
                spans.extend((e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for evs in devices.values():
        evs.sort(key=lambda e: e.start)
    spans.sort(key=lambda s: s[1])
    if not spans:
        raise ValueError("the trace holds no bench.* host spans")
    return Trace(devices=devices, spans=spans)


def to_json(trace: Trace) -> dict:
    """A trace as plain data (for recorded fixtures)."""
    return {"devices": {k: [[e.name, e.start, e.end] for e in evs]
                        for k, evs in trace.devices.items()},
            "spans": [list(s) for s in trace.spans]}


def from_json(data: dict) -> Trace:
    return Trace(devices={k: [DeviceEvent(*e) for e in evs]
                          for k, evs in data["devices"].items()},
                 spans=[tuple(s) for s in data["spans"]])


def union(intervals: Iterable[Interval], clip: Interval) -> List[Interval]:
    """Disjoint sorted union of ``intervals`` clipped to ``clip``."""
    lo, hi = clip
    out: List[Interval] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_s(trace: Trace, chips: int) -> float:
    """Seconds in the window with an op running, averaged over the chips
    used (the planes with most busy time)."""
    per = sorted((sum(b - a for a, b in union(
        ((e.start, e.end) for e in evs), trace.window))
        for evs in trace.devices.values()), reverse=True)
    used = per[:chips]
    return sum(used) / len(used) if used else 0.0


def _in_window(trace: Trace, evs: Sequence[DeviceEvent]):
    a, b = trace.window
    return [e for e in evs if e.start >= a and e.end <= b]


def kernel_s(trace: Trace, pattern: str) -> Tuple[float, int]:
    """(summed seconds, count) of the window's ops whose name matches."""
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for evs in trace.devices.values():
        for e in _in_window(trace, evs):
            if rx.search(e.name):
                total += e.end - e.start
                n += 1
    return total, n


def short_name(hlo: str) -> str:
    """``%fusion.71 = bf16[64,3352]{...} fusion(...)`` ->
    ``%fusion.71 = bf16[64,3352] fusion``."""
    head, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo[:120]
    m = re.search(r"\s([a-z][\w\-]*)\(", rest)
    shape = re.sub(r"\{[^}]*\}", "", rest[:m.start()] if m else rest)
    return f"{head} = {shape} {m.group(1) if m else ''}".strip()[:120]


def self_times(trace: Trace) -> Dict[str, float]:
    """Self seconds per short op name over the window, all devices."""
    out: Dict[str, float] = collections.Counter()
    for evs in trace.devices.values():
        stack: List[List] = []          # [event, child seconds]
        for e in _in_window(trace, evs) + [None]:
            while stack and (e is None or e.start >= stack[-1][0].end):
                done, child = stack.pop()
                dur = done.end - done.start
                out[short_name(done.name)] += dur - child
                if stack:
                    stack[-1][1] += dur
            if e is not None:
                stack.append([e, 0.0])
    return out


def idle_gaps(trace: Trace) -> List[Tuple[str, float]]:
    """Idle seconds of the window by the host span that overlaps each gap
    most, as ``("<span> x<gaps>", seconds)``, largest first."""
    a, b = trace.window
    busy = union(((e.start, e.end) for evs in trace.devices.values()
                  for e in evs), trace.window)
    gaps, t = [], a
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < b:
        gaps.append((t, b))
    spans = trace.spans               # sequential host spans, by start
    total: Dict[str, float] = collections.Counter()
    count: Dict[str, int] = collections.Counter()
    j = 0
    for g0, g1 in gaps:
        while j < len(spans) and spans[j][2] <= g0:
            j += 1
        best, label = 0.0, "none"
        for name, s0, s1 in spans[j:]:
            if s0 >= g1:
                break
            ov = min(g1, s1) - max(g0, s0)
            if ov > best:
                best, label = ov, name
        total[label] += g1 - g0
        count[label] += 1
    return [(f"{k} x{count[k]}", v) for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])]


def breakdown(trace: Trace, top: int = 10) -> dict:
    ops = sorted(self_times(trace).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle_gaps(trace)[:top]]}


def steps_in(trace: Trace) -> int:
    return sum(1 for name, _, _ in trace.spans if name == "step")
