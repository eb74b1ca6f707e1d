"""decode_device_ms.<mix>: device busy time in the traced window over the
decode steps in it (``bench.step`` spans in the trace)."""
import tracereduce


def read(run, metric):
    t = run.trace
    if not t or not t.devices:
        return None
    n = tracereduce.steps_in(t) if t else 0
    return 1e3 * tracereduce.busy_s(t, run.chips) / n if n else None
