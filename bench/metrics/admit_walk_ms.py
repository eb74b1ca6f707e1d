"""admit_walk_ms.<mix>: host time in the program's ``serve.admit`` and
``serve.walk`` spans per decode step in the traced window, in ms: admission
and the per-slot bookkeeping after the step's tokens are back.  Read on the
host's clock (``programspans``); none without the spans."""
import programspans

programspans.install()


def read(run, metric):
    return programspans.host_ms_per_step(run.trace,
                                         ("serve.admit", "serve.walk"))
