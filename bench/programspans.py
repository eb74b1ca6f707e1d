"""The program's own spans and the device's program runs in a profiler
trace, and what the metrics read from them.

``BatchedServer.step`` writes ``jax.profiler.TraceAnnotation`` spans named
``serve.<part>`` into the host planes of the same trace that holds the
benchmark's ``bench.*`` spans: ``serve.step`` around each decode step, whose
stats are the server's cumulative counters at entry (``step``,
``slot_steps``, ``prompt_tokens``), and inside it ``serve.admit``,
``serve.dispatch``, ``serve.sync`` (the host blocked on the device) and
``serve.walk``.  A device plane's ``XLA Modules`` line holds one event per
program run on the device (``jit_decode_step(<id>)``, ``jit__argmax(<id>)``).

``tracereduce.load`` keeps neither.  ``install()`` has it also set
``trace.program``, the ``serve.*`` spans as ``(name, start, end, stats)`` by
start, in seconds, and ``trace.modules``, each device plane's program runs
as ``(start, end)``; both are empty for a trace that holds none.  The
readers that need them call ``install()`` when they are loaded, which is
before the run loads its trace; nothing ``tracereduce`` returned before
changes.

Each quantity is read on one clock: the host's spans against each other, or
a device plane's operations against its own programs.  The host and device
clocks of one trace can sit a millisecond apart, so no quantity here places
a device interval against a host span (only the window's two ends, as
``idle_share`` does).

- Decode steps are the ``serve.dispatch`` spans that start in the window.
- Host: the time in ``serve.dispatch``, and in ``serve.admit`` with
  ``serve.walk``, per decode step.
- Device: the window's idle time (outside the busy union, on the planes
  ``busy_s`` averages over) splits into the part inside a program run,
  between the operations of one program, and the part between program
  runs, where the device waits for the host's next dispatch; the two add up
  to window less busy.
- The prompt share is the growth of ``prompt_tokens`` over that of
  ``slot_steps`` from the first ``serve.step`` in the window to the last.
"""
from __future__ import annotations

import functools
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

import tracereduce as T

PREFIX = "serve."
MODULES_LINE = "XLA Modules"

Span = Tuple[str, float, float, dict]     # (name, start, end, stats)


def collect(trace_dir: str) -> Tuple[List[Span], Dict[str, List[T.Interval]]]:
    """The ``serve.*`` host events by start, and each device plane's
    program runs, of the newest trace under ``trace_dir`` (the file
    ``tracereduce.load`` reads)."""
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(paths[-1])
    spans: List[Span] = []
    modules: Dict[str, List[T.Interval]] = {}
    for plane in data.planes:
        device = plane.name.startswith(T.DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name == MODULES_LINE:
                modules.setdefault(plane.name, []).extend(
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
            elif not device:
                spans.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
                    for e in line.events if e.name.startswith(PREFIX))
    return sorted(spans, key=lambda s: s[1]), modules


def install() -> None:
    """Have ``tracereduce.load`` also set ``trace.program`` and
    ``trace.modules``."""
    import tracereduce          # the module the run imports, found anew
    load = tracereduce.load
    if getattr(load, "keeps_program", False):
        return

    @functools.wraps(load)
    def load_with_program(trace_dir: str) -> T.Trace:
        trace = load(trace_dir)
        trace.program, trace.modules = collect(trace_dir)
        return trace

    load_with_program.keeps_program = True
    tracereduce.load = load_with_program


def program(trace: T.Trace) -> List[Span]:
    return getattr(trace, "program", [])


def modules(trace: T.Trace) -> Dict[str, List[T.Interval]]:
    return getattr(trace, "modules", {})


def from_json(data: dict) -> T.Trace:
    """``tracereduce.from_json`` (for recorded and made-up traces), with the
    program's spans from the key ``program`` and the program runs from
    ``modules``, either of which may be absent."""
    trace = T.from_json(data)
    trace.program = [tuple(s) for s in data.get("program", [])]
    trace.modules = {k: [tuple(m) for m in ms]
                     for k, ms in data.get("modules", {}).items()}
    return trace


def _in_window(trace: T.Trace, names: Sequence[str]) -> List[Span]:
    a, b = trace.window
    return [s for s in program(trace) if s[0] in names and a <= s[1] < b]


def decode_steps(trace: T.Trace) -> int:
    return len(_in_window(trace, ("serve.dispatch",)))


def host_ms_per_step(trace: Optional[T.Trace], names: Sequence[str]
                     ) -> Optional[float]:
    """Host milliseconds per decode step in the spans named ``names`` that
    start in the window; None without decode steps."""
    n = decode_steps(trace) if trace else 0
    if not n:
        return None
    return 1e3 * sum(e - s for _, s, e, _ in _in_window(trace, names)) / n


def _length(intervals: List[T.Interval]) -> float:
    return sum(b - a for a, b in intervals)


def idle_split(trace: T.Trace, chips: int
               ) -> Optional[Tuple[float, float]]:
    """(inside, between) program runs: idle seconds of the window, averaged
    over the planes ``busy_s`` uses; None where those planes hold no
    program runs."""
    busy = sorted(((_length(T.union(((e.start, e.end) for e in evs),
                                    trace.window)), plane)
                   for plane, evs in trace.devices.items()),
                  key=lambda b: -b[0])[:chips]
    runs = modules(trace)
    if not busy or not all(runs.get(plane) for _, plane in busy):
        return None
    inside = between = 0.0
    for busy_s, plane in busy:
        # a program run's time not covered by an operation is idle inside it
        covered = _length(T.union(
            [(e.start, e.end) for e in trace.devices[plane]] + runs[plane],
            trace.window))
        inside += covered - busy_s
        between += trace.window_s - covered
    return inside / len(busy), between / len(busy)


def gap_ms_per_step(trace: Optional[T.Trace], chips: int
                    ) -> Optional[Tuple[float, float]]:
    """(inside, between) program runs: device idle milliseconds per decode
    step in the window; None where the trace has no program runs or no
    ``serve.*`` spans."""
    if not trace or not trace.devices:
        return None
    n = decode_steps(trace)
    split = idle_split(trace, chips)
    if not n or split is None:
        return None
    return tuple(1e3 * s / n for s in split)


def prompt_share(trace: Optional[T.Trace]) -> Optional[float]:
    """% of the slot-steps from the window's first ``serve.step`` to its
    last that fed a prompt token; None with fewer than two."""
    steps = [s[3] for s in _in_window(trace, ("serve.step",))] \
        if trace else []
    if len(steps) < 2:
        return None
    slots = steps[-1]["slot_steps"] - steps[0]["slot_steps"]
    if slots <= 0:
        return None
    return 100.0 * (steps[-1]["prompt_tokens"] -
                    steps[0]["prompt_tokens"]) / slots
