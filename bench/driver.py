"""The client: drives ``BatchedServer.submit``/``step`` through the window.

Open loop: each planned request is submitted once its scheduled arrival has
passed, whether or not earlier ones finished; between steps the client
sleeps only when the server has nothing to do.  After the window the client
keeps stepping, without new arrivals, until every request due in the window
has its first token and enough requests have finished to compare, or until
the cap.  An offline job's requests still queued at the close are taken
back: they were never attempted.  Every call into the server sits in a host span (``submit``,
``step``, ``poll``, ``wait``), timed with ``perf_counter`` and, in a traced
run, also written into the profiler's trace as ``bench.<span>``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, List, Optional

import jax

from loadgen import Planned


@dataclasses.dataclass
class Record:
    """What the client saw of one request; times in s from window start."""
    planned: Planned
    request: object = None              # repro.runtime.serve.Request
    sent: Optional[float] = None
    admitted_at: Optional[float] = None  # start of the step that admitted it
    admitted_seen: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    withdrawn: bool = False

    @property
    def first_token(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None


@dataclasses.dataclass
class StepRecord:
    start: float
    end: float
    contexts: List[int]  # each occupied slot's length (tokens attended)


@dataclasses.dataclass
class Window:
    seconds: float
    records: List[Record]
    steps: List[StepRecord]
    spans: List[tuple]               # (name, start, end), window clock
    lateness: List[float]            # sent - scheduled, per request
    trace_interval: Optional[tuple] = None
    compiles_in_window: int = 0
    drained_at: Optional[float] = None

    def due(self) -> List[Record]:
        """The requests the window owes an answer: those that arrived in it,
        less any taken back at the close (``_withdraw``)."""
        return [r for r in self.records if r.planned.arrival_s < self.seconds
                and not r.withdrawn]


class Client:
    def __init__(self, server, make_request: Callable[[Planned], object],
                 clock=time.perf_counter, annotate: bool = False):
        self.server = server
        self.make_request = make_request
        self.clock = clock
        self.annotate = annotate
        self.t0 = 0.0
        self.spans: List[tuple] = []

    @contextlib.contextmanager
    def _span(self, name: str):
        """Record ``(name, start, end)``; in a traced run also annotate the
        profiler's trace with ``bench.<name>``."""
        ann = jax.profiler.TraceAnnotation(f"bench.{name}") \
            if self.annotate else contextlib.nullcontext()
        with ann:
            span = [self.clock() - self.t0]
            try:
                yield span
            finally:
                self.spans.append((name, span[0], self.clock() - self.t0))

    def run(self, planned: List[Planned], seconds: float, *,
            drain_cap_s: float, min_finished: int,
            withdraw_at_close: bool = False,
            on_tick: Optional[Callable[[float], None]] = None,
            compile_counter: Optional[Callable[[], int]] = None) -> Window:
        """Serve ``planned`` through a window of ``seconds``, then drain."""
        srv = self.server
        records = [Record(p) for p in planned]
        live: List[Record] = []
        steps: List[StepRecord] = []
        lateness: List[float] = []
        nxt = 0
        c0 = compile_counter() if compile_counter else 0
        compiles = 0
        self.spans = []
        self.t0 = self.clock()
        drained_at = None
        closed = False
        while True:
            now = self.clock() - self.t0
            in_window = now < seconds
            if not in_window and not closed:
                closed = True
                if compile_counter:
                    compiles = compile_counter() - c0
                if withdraw_at_close:
                    live = self._withdraw(live)
            if on_tick:
                on_tick(now)
            if in_window or (nxt < len(records) and
                             records[nxt].planned.arrival_s < seconds):
                # a request due in the window is sent, late if need be
                with self._span("submit"):
                    while nxt < len(records) and \
                            records[nxt].planned.arrival_s <= now and \
                            records[nxt].planned.arrival_s < seconds:
                        rec = records[nxt]
                        rec.request = self.make_request(rec.planned)
                        srv.submit(rec.request)
                        rec.sent = self.clock() - self.t0
                        lateness.append(rec.sent - rec.planned.arrival_s)
                        live.append(rec)
                        nxt += 1
            if not in_window and (
                    self._drained(records, live, seconds, min_finished) or
                    now >= seconds + drain_cap_s):
                drained_at = now
                break
            if live:
                before = srv.steps
                with self._span("step") as span:
                    srv.step()
                end = self.clock() - self.t0
                with self._span("poll"):
                    live = self._poll(live, before, span[0], end, steps)
            elif in_window:
                wake = records[nxt].planned.arrival_s if nxt < len(records) \
                    else seconds
                with self._span("wait"):
                    time.sleep(max(0.0, min(wake, seconds) - now))
            else:
                drained_at = now
                break
        if compile_counter and not closed:
            compiles = compile_counter() - c0
        return Window(seconds=seconds, records=records, steps=steps,
                      spans=list(self.spans), lateness=lateness,
                      compiles_in_window=compiles, drained_at=drained_at)

    def _withdraw(self, live: List[Record]) -> List[Record]:
        """Take back the requests still queued: an offline job's surplus
        was never attempted."""
        kept = []
        for rec in live:
            if rec.request.started is None:
                self.server.queue.remove(rec.request)
                rec.sent = None
                rec.withdrawn = True
            else:
                kept.append(rec)
        return kept

    @staticmethod
    def _drained(records, live, seconds, min_finished) -> bool:
        due = [r for r in records if r.planned.arrival_s < seconds]
        if any(r.sent is not None and r.first_token is None for r in due):
            return False
        done = sum(r.finished is not None for r in records)
        return done >= min_finished or not live

    @staticmethod
    def _poll(live, step_index, start, end, steps) -> List[Record]:
        """Record what the step just served; return the requests still live.

        ``Request.started`` and ``.finished`` count decode steps, so the
        step numbered ``step_index`` ran for the requests in slots then, each
        at context ``step_index - started + 1``.
        """
        contexts = []
        still = []
        for rec in live:
            r = rec.request
            if r.started is not None and r.started <= step_index and \
                    (r.finished is None or r.finished > step_index):
                contexts.append(step_index - r.started + 1)
            if r.started is not None and rec.admitted_at is None:
                rec.admitted_at, rec.admitted_seen = start, end
            new = len(r.output) - len(rec.token_times)
            if new > 0:
                rec.token_times.extend([end] * new)
            if r.done:
                rec.finished = end
            else:
                still.append(rec)
        steps.append(StepRecord(start, end, contexts))
        return still
