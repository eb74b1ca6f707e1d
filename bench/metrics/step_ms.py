"""step_ms.<mix>: mean host time of one ``BatchedServer.step()`` call that
ran a decode step, over the traced interval (benchmark span, host clock)."""


def read(run, metric):
    on, off = run.window.trace_interval
    d = [s.end - s.start for s in run.window.steps if on <= s.start < off]
    return 1e3 * sum(d) / len(d) if d else None
