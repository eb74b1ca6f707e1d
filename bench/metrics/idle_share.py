"""idle_share.<mix>: share of the traced window, in %, in which no device
operation ran: 1 - busy union / window."""
import tracereduce


def read(run, metric):
    t = run.trace
    if not t or not t.devices:
        return None
    if not t or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - tracereduce.busy_s(t, run.chips) / t.window_s)
