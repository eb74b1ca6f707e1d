"""tokens_per_s: prompt tokens ingested plus tokens generated inside the
window, over the window.  A generated token counts when the client sees it.
A request's prompt tokens are spread evenly from the start of the step that
admitted it to its first token, and the part of that interval inside the
window counts."""


def read(run, metric):
    w = run.window
    total = 0.0
    for r in w.records:
        total += sum(1 for t in r.token_times if t < w.seconds)
        a, f = r.admitted_at, r.first_token
        if a is None or f is None or a >= w.seconds:
            continue
        inside = (min(f, w.seconds) - a) / (f - a) if f > a else 1.0
        total += len(r.planned.prompt) * inside
    return total / w.seconds
