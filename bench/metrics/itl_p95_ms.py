"""itl_p95_ms: 95th percentile of every gap between consecutive output
tokens of a request, over all gaps that end inside the window."""
import numpy as np


def read(run, metric):
    gaps = []
    for r in run.window.records:
        t = r.token_times
        gaps.extend(b - a for a, b in zip(t, t[1:]) if b < run.window.seconds)
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
